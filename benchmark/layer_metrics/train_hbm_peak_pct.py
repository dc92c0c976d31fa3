"""The most device memory the process held, as a share of what the
allocator may hand out: ``peak_bytes_in_use`` (the harness reads it
after the window into ``ctx["device"]["memory_peak_bytes"]``) over the
first device's ``bytes_limit``. None where the backend reports no
allocator statistics."""


def read(ctx):
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    peak = ctx["device"].get("memory_peak_bytes")
    limit = stats.get("bytes_limit")
    if not peak or not limit:
        return None
    return 100.0 * peak / limit
