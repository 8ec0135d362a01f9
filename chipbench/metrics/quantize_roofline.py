"""Share of its memory roofline that the int8 uplink's Pallas kernel
reaches on chip 0, in %: for each of its runs in the window, the bytes of
its HBM-resident arguments and outputs (chipbench.flops.hbm_bytes, from
the shapes in the operation's HLO text) over the chip's HBM bandwidth,
summed, over the summed device time of those runs. The kernel does a few
operations per byte, so bandwidth bounds it. The kernel is the TPU
custom call whose outputs are the int8 values and their float32 scales."""
from chipbench import flops, trace


def is_kernel(name: str) -> bool:
    head, _, rest = name.partition(" = ")
    return ('custom_call_target="tpu_custom_call"' in rest
            and rest.startswith("(s8["))


def read(ctx):
    runs = [e for e in trace.in_window(ctx.trace, ctx.trace.ops.get(0, []))
            if is_kernel(e.name)]
    busy = sum(e.dur for e in runs) * 1e-9
    if busy <= 0:
        return None
    least = sum(flops.hbm_bytes(e.name) for e in runs) / ctx.peaks[
        "hbm_bytes_per_s"]
    return 100.0 * least / busy
