"""Platform/env staging: the ONE place that sets jax platform env vars.

jax reads ``XLA_FLAGS`` / ``JAX_PLATFORMS`` / ``JAX_ENABLE_X64`` when the
backend first initializes (the first ``jax.devices()`` / array op — *not*
at import), and the resulting device topology is locked for the process.
Code that needs a forced topology therefore has exactly two options:
stage the env vars before anything initializes the backend, or start a
fresh process. Historically each call site mutated ``os.environ``
directly (``launch/dryrun.py`` clobbered a user's ``XLA_FLAGS`` outright;
every distributed test pasted its own prelude) — this module replaces
all of them:

* :func:`stage` — idempotent env staging that *composes* with an
  existing ``XLA_FLAGS`` (other flags survive; stale spellings of the
  same flag are replaced). Raises if the backend already initialized
  with a conflicting topology, and no-ops when the env already matches.
* :func:`device_mesh` — a 1-D device mesh over the first ``n``
  devices JAX sees (what ``make_index(..., mesh=n)`` builds).
* :func:`simulate_mesh` — the ``--mesh N`` entry point: stages ``n``
  forced host devices, then builds the :func:`device_mesh` (on an
  accelerator host over the real chips, on the CPU over those).
  An 8-device CPU mesh exercises the full shard_map exchange
  (all_to_all/all_gather/psum routing) on a laptop or CI runner; see
  tests/helpers.py ``run_on_simulated_mesh`` for the subprocess fixture
  that guarantees the early-import requirement.
* :func:`use_compile_cache` — turn on JAX's persistent compilation
  cache from an entry point's ``main`` (never at import).

Keep this module light: importing it must not initialize (or require)
jax — :func:`stage` is pure env-var bookkeeping until something asks
for devices.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

HOST_DEVICE_FLAG = "--xla_force_host_platform_device_count"
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# fixed path: the cache directory is part of every entry's key, so one
# that moved between runs would never hit
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def jax_initialized() -> bool:
    """True once any jax backend has been created (topology locked).

    Checks the backend cache of an *already imported* jax — importing
    jax here would defeat the whole point of env staging."""
    bridge = sys.modules.get("jax._src.xla_bridge")
    if bridge is None:
        return False
    return bool(getattr(bridge, "_backends", None))


def _merge_xla_flags(new_flags: dict[str, str],
                     existing: str | None = None) -> str:
    """Compose ``new_flags`` ({"--flag": "value"}) into an existing
    ``XLA_FLAGS`` string: unrelated user flags survive, stale spellings
    of a staged flag are replaced (never duplicated)."""
    if existing is None:
        existing = os.environ.get("XLA_FLAGS", "")
    kept = [tok for tok in existing.split()
            if tok.split("=", 1)[0] not in new_flags]
    kept.extend(f"{flag}={val}" for flag, val in new_flags.items())
    return " ".join(kept)


def staged_host_device_count() -> int | None:
    """The forced host device count currently in ``XLA_FLAGS`` (None if
    not staged)."""
    for tok in os.environ.get("XLA_FLAGS", "").split():
        name, _, val = tok.partition("=")
        if name == HOST_DEVICE_FLAG and val:
            try:
                return int(val)
            except ValueError:
                return None
    return None


def stage(*, host_device_count: int | None = None,
          platform: str | None = None,
          enable_x64: bool | None = None) -> None:
    """Stage platform env vars; must run before jax initializes.

    Composes with (never clobbers) an existing ``XLA_FLAGS``. Safe to
    call repeatedly, and a no-op when the requested config is already
    in effect — so library entry points (``launch/dryrun``, the driver's
    ``--mesh`` flag) can call it unconditionally. Raises ``RuntimeError``
    when jax already initialized with a *conflicting* topology: the
    caller must stage earlier (or run in a subprocess — see
    tests/helpers.py)."""
    if host_device_count is not None:
        already = staged_host_device_count() == int(host_device_count)
        if jax_initialized() and not already:
            import jax  # already imported (jax_initialized saw it)
            have = len(jax.devices())
            if have != int(host_device_count):
                raise RuntimeError(
                    f"jax already initialized with {have} device(s); "
                    f"cannot force host_device_count="
                    f"{host_device_count} now. Stage the platform "
                    f"before the first jax.devices()/array op "
                    f"(import repro.configs.platform first), or run "
                    f"in a fresh process "
                    f"(tests/helpers.py:run_on_simulated_mesh).")
        if not already:
            os.environ["XLA_FLAGS"] = _merge_xla_flags(
                {HOST_DEVICE_FLAG: str(int(host_device_count))})
    if platform is not None:
        if jax_initialized() and \
                os.environ.get("JAX_PLATFORMS", "") != platform:
            raise RuntimeError(
                f"jax already initialized; cannot switch platform to "
                f"{platform!r} now")
        os.environ["JAX_PLATFORMS"] = platform
    if enable_x64 is not None:
        want = "1" if enable_x64 else "0"
        if jax_initialized() and \
                os.environ.get("JAX_ENABLE_X64") != want:
            raise RuntimeError(
                "jax already initialized; cannot toggle x64 now")
        os.environ["JAX_ENABLE_X64"] = want


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    no other directory is set here; otherwise the cache goes to
    ``.jax_cache/`` at the checkout root. Every compiled program is kept
    (no minimum compile time), so a second run of the same entry point
    compiles nothing. Call from ``main``, never at import."""
    import jax
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def device_mesh(n: int, axis_names: tuple[str, ...] = ("data",)):
    """A 1-D ``Mesh`` over the first ``n`` devices JAX sees: on a TPU
    host its chips, on the CPU the staged host devices. Raises when
    fewer than ``n`` exist; it never builds a smaller mesh.

    ``make_index(..., mesh=n)`` builds its mesh here, so a
    configuration file can name a chip count."""
    import jax
    import numpy as np
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"a mesh of {n} devices was asked for, but JAX sees only "
            f"{len(devs)} {devs[0].platform} device(s). On the CPU, "
            f"stage the host device count before jax initializes "
            f"(simulate_mesh, or tests/helpers.py:run_on_simulated_mesh).")
    return jax.sharding.Mesh(np.asarray(devs[:n]), axis_names)


def simulate_mesh(n: int, axis_names: tuple[str, ...] = ("data",)):
    """:func:`device_mesh` after staging ``n`` forced host devices.

    Where an accelerator is present (a TPU host) the mesh is over its
    chips, and the staged host device count is ignored by the
    accelerator backend. On the CPU the ``n`` staged host devices are
    CI's simulated pod.

    Must be the first jax-touching call of the process (the subprocess
    fixture in tests/helpers.py guarantees this for tests; the serving
    driver's ``--mesh N`` flag calls it before building anything)."""
    stage(host_device_count=n)
    return device_mesh(n, axis_names)
