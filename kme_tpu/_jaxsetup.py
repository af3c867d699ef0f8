"""One-time JAX configuration for the device-side modules.

int64 is part of the engine's data model (Java `long` balances/ids,
KProcessor.java:30-33, 451-455). JAX downcasts to int32 unless x64 is
enabled; device modules import this module before touching jax.numpy.
The hot matching path still uses explicit int32 arrays — only ledger
arithmetic is 64-bit. Pure-Python layers (wire/oracle/workload) do not
import this, so they stay usable without JAX.

Two more process-wide decisions live here so that no device module
makes them on its own:

- the compile cache: JAX's persistent compilation cache is always on.
  Where `JAX_COMPILATION_CACHE_DIR` is set JAX reads it and nothing is
  set here; otherwise the cache sits at `<checkout>/.jax_cache`, a
  fixed path derived from this file (the directory is part of the
  cache key's world: a path that moves never hits). Every process that
  imports the package — kme-serve, its supervised restarts, standbys,
  drills — shares it.
- the backend (`backend()` / `interpret()`): the TPU, or the CPU with
  the Pallas interpreter ONLY when the environment asked for the CPU.
  JAX registers the TPU plug-in with fail_quietly=True, so a process
  that cannot get the chip (none present, or another process holds it)
  would otherwise come up on the CPU and serve byte-exact output from
  the interpreter without a word.
"""

import functools
import os
import time

import jax
import jax.monitoring

jax.config.update("jax_enable_x64", True)


def _process_age_s() -> float:
    """Seconds since this process was created (Linux /proc; 0.0 where
    that cannot be read)."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return max(0.0, up - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


# what the process paid before it could serve, for the heartbeat's
# start-up gauges: process start -> jax imported (here), then the first
# backend check (describe(), below)
startup = {"startup_import_s": round(_process_age_s(), 3),
           "startup_backend_s": 0.0}

# every program XLA compiled (or took from the persistent cache) in this
# process: JAX's own compile-duration event, the one its debug log
# prints as "Finished XLA compilation of ..."
compiles = {"n": 0, "seconds": 0.0}


def _on_duration(event: str, seconds: float, **_kw) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        compiles["n"] += 1
        compiles["seconds"] += seconds


jax.monitoring.register_event_duration_secs_listener(_on_duration)

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(CHECKOUT, ".jax_cache"))
# store every program, not only those that took a second to compile:
# a restarted server should find the scan program AND the small
# slice/zero-fill programs around it
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def cache_dir() -> str:
    """Where compiled programs are kept (from the environment or the
    checkout default above)."""
    return jax.config.jax_compilation_cache_dir


@functools.lru_cache(maxsize=None)
def backend() -> str:
    """'tpu', or 'cpu' when — and only when — the first platform named
    by JAX_PLATFORMS / jax_platforms is cpu. Anything else raises: no
    platform named and no TPU came up (with JAX's own initialisation
    error), or a platform this code has no kernels for."""
    asked = (jax.config.jax_platforms or "").split(",")[0].strip().lower()
    if asked == "cpu":
        return "cpu"
    if asked not in ("", "tpu"):
        raise RuntimeError(
            f"kme_tpu runs on the TPU, or on the CPU (Pallas interpreter) "
            f"when JAX_PLATFORMS=cpu asks for it; JAX_PLATFORMS names "
            f"{asked!r}")
    got = jax.default_backend()   # an explicit 'tpu' raises in here
    if got != "tpu":
        from jax._src import xla_bridge

        why = xla_bridge._backend_errors.get("tpu", "no TPU plug-in found")
        raise RuntimeError(
            f"kme_tpu: no TPU backend (JAX came up on {got!r}): {why}. "
            f"One process holds a chip at a time. Set JAX_PLATFORMS=cpu "
            f"to run on the CPU under the Pallas interpreter (tests).")
    return "tpu"


def interpret() -> bool:
    """Pallas kernels run under the interpreter iff the backend is the
    (explicitly requested) CPU."""
    return backend() != "tpu"


def describe() -> dict:
    """What a device process runs on, for its start-up line and
    heartbeat. Resolves the backend, so it raises like backend()."""
    t0 = time.perf_counter()
    platform = backend()
    devs = jax.devices()
    if not startup["startup_backend_s"]:
        startup["startup_backend_s"] = round(time.perf_counter() - t0, 3)
    return {"backend": platform, "interpret": interpret(),
            "device_kind": devs[0].device_kind, "device_count": len(devs),
            "compile_cache_dir": cache_dir()}
