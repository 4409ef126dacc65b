"""Share of the HBM roofline, in %: the least time the chip could take to
read the touched columns once (touched_bytes.py over the device's peak
bandwidth, peaks.json) over the device time a statement took. Memory
bound: these queries do a few operations a byte."""


def read(ctx, state):
    from readers import trace_device_ms
    from touched_bytes import touched_bytes

    dev_ms = trace_device_ms.read(ctx, None)
    nbytes = touched_bytes(ctx.loaded, ctx.mix["oracle"])
    if not dev_ms or not nbytes:
        return None
    least_ms = 1e3 * nbytes / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_ms / dev_ms
