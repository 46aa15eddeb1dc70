"""MB (10^6 bytes) of the optional per-pod operands (the forbidden mask,
the score, the exclusive flags) that the service's pad handed to the
upload per solve, padded to the shape bucket: the program's own
stats.operand_bytes (solver/service.py, also the counter
solve_operand_bytes_total{operand}), over the window as the driver
records it, over the solves. None where the program keeps no such
count."""


def read(ctx):
    layers = ctx.window.layers
    counted = layers.get("operand_bytes")
    if counted is None or not layers.get("solves"):
        return None
    return sum(counted.values()) / layers["solves"] / 1e6
