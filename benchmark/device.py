"""What both kinds ask of the device they run on."""

from __future__ import annotations


def memory_peak(stats: dict | None) -> int:
    """Peak bytes on one device. The TPU allocator counts a running
    program's temporaries as `reserved`, apart from the buffers `in use` (a
    train step with 9.9 GB of temporaries reads 1.5 GB in use and 9.9 GB
    reserved; my chip run, PR 24). So the peak is the larger of the buffers'
    own peak and the buffers now live plus the largest reservation."""
    stats = stats or {}
    return max(int(stats.get("peak_bytes_in_use", 0)),
               int(stats.get("bytes_in_use", 0))
               + int(stats.get("peak_bytes_reserved", 0)))


def profiler_options():
    """Device and host (TraceMe) events, without the Python call tracer,
    which slows the host several times over and so inflates idle time."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts
