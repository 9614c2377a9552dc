"""The chip, or an exit; the compile cache; memory readings."""
import os
import sys

from .loader import ROOT
from .peaks import peaks_for

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CACHE_DIR = ROOT / ".jax_cache"


def require_tpu(chips):
    """The devices the cell runs on, or an exit with another code than 0:
    the benchmark has no CPU branch."""
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        sys.exit(f"benchmarks/run.py: jax.default_backend() is {backend!r},"
                 " not 'tpu': a cell measures the chip and has no CPU path")
    devices = jax.devices()
    if len(devices) < chips:
        sys.exit(f"benchmarks/run.py: the cell asks for {chips} chip(s), "
                 f"jax sees {len(devices)}")
    return devices[:chips]


def enable_compile_cache():
    """jax's persistent compilation cache at a fixed place inside the
    checkout (the path is part of the key), or where the environment says.
    Every program is cached, however small or quick to compile."""
    import jax

    if not os.environ.get(CACHE_ENV):
        # the benchmark's own directory, and no eviction in it: a size cap
        # from the machine's environment would turn on jax's LRU book-keeping
        # (an -atime file beside every entry), and one entry without it makes
        # every later write fail (seen on the chip machine, PR 25)
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax.config.jax_compilation_cache_dir


def context(cell, devices, seed, seconds, trace, t_start):
    """What a driver is handed: the cell (``loader.load_cell``) with the
    run's arguments, its devices and their peaks."""
    return dict(cell, seed=seed, seconds=seconds, trace=bool(trace),
                devices=devices, chips=len(devices), t_start=t_start,
                peaks=peaks_for(devices[0].device_kind))


def memory_peak_bytes(devices):
    """Peak bytes in use on the fullest chip."""
    return max(int(d.memory_stats()["peak_bytes_in_use"]) for d in devices)


def memory_line(tag, devices):
    for d in devices:
        s = d.memory_stats()
        print(f"memory {tag} [{d.id}]: in use "
              f"{s['bytes_in_use'] / 2**30:.2f} GiB, peak "
              f"{s['peak_bytes_in_use'] / 2**30:.2f} GiB of "
              f"{s['bytes_limit'] / 2**30:.2f} GiB", flush=True)


def device_block(devices):
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
