"""The service's upload stage per solve, in ms: its own
stage_seconds{upload} (solver/service.py: on the one-chip Pallas path
the `jax.device_put` call alone, the host side of the operands'
transfer, which may go on under the kernel's launch; 0 for a solve whose
operands the device already holds), summed over the window by the
benchmark's wrapper of its stage recorder, over the solves. The harness
reads it in a traced run, so it is the stage under the profiler, which
slows the transfer's host side; the untraced sum is on the run's log
line `stages`."""


def read(ctx):
    layers = ctx.window.layers
    seconds = layers.get("stages", {}).get("upload")
    if seconds is None or not layers.get("solves"):
        return None
    return seconds / layers["solves"] * 1e3
