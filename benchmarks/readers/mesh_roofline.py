"""Share of the HBM roofline of a cell whose node spans several chips, in
%: the least time the chips TOGETHER could take to read the touched columns
once (touched_bytes.py over chips x one chip's peak bandwidth, peaks.json)
over the device time a statement took (`trace_device_ms`: busy time
averaged over the chips). `hbm_roofline.py` divides by one chip's bandwidth;
here every chip reads a quarter of the bytes at the same time."""


def read(ctx, state):
    from readers import trace_device_ms
    from touched_bytes import touched_bytes

    dev_ms = trace_device_ms.read(ctx, None)
    nbytes = touched_bytes(ctx.loaded, ctx.mix["oracle"])
    chips = (ctx.trace or {}).get("chips")
    if not dev_ms or not nbytes or not chips:
        return None
    least_ms = 1e3 * nbytes / (chips * ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least_ms / dev_ms
