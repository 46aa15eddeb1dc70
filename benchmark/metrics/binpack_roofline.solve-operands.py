"""The bin-pack's share of its roofline with its optional operands, in
%: the least time the chip could take for one solve's work, operands
included (benchmark/workcount_operands.py, counted from the shapes),
over the device time the bin-pack programs took per solve
(binpack_device_ms.py). The log line names the bound that governs."""

import sys

from benchmark import harness, workcount_operands


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    device_ms = harness.load_reader(ctx.run, "binpack_device_ms.solve-operands").read(ctx)
    if not device_ms:
        return None
    seconds, bound = workcount_operands.least_time(ctx.window.layers["shape"], ctx.peaks)
    print(f"binpack roofline with operands: least time {seconds * 1e6:.3f} us per solve, "
          f"{bound}-bound", file=sys.stderr)
    return 100.0 * seconds / (device_ms / 1e3)
