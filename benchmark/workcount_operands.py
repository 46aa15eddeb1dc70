"""The work a bin-pack solve needs with its optional per-pod operands,
counted from its shapes: benchmark/workcount.py's count plus, for each
operand the window's `shape` marks present (P pod rows, T groups):

- `forbidden` (required node affinity, bool[P, T]): P*T bytes read and
  P*T operations, the mask applied to the fit test;
- `score` (preferred node affinity, f32[P, T]): 4*P*T bytes read and
  2*P*T operations, the select of feasible scores and the compare
  against the row's best;
- `exclusive` (one-per-node rows, bool[P]): P bytes read.

The shapes are the logical ones, so one count holds for whatever
implements the kernel. With no operand present it is workcount's.
"""

from __future__ import annotations

from benchmark import workcount


def _args(shape: dict) -> tuple:
    return (shape["pods"], shape["groups"], shape["resources"],
            shape["taint_keys"], shape["label_keys"])


def binpack_ops(shape: dict) -> float:
    pods, groups = shape["pods"], shape["groups"]
    ops = workcount.binpack_ops(*_args(shape))
    if shape.get("forbidden"):
        ops += float(pods * groups)
    if shape.get("score"):
        ops += 2.0 * pods * groups
    return ops


def binpack_bytes(shape: dict) -> float:
    pods, groups = shape["pods"], shape["groups"]
    nbytes = workcount.binpack_bytes(*_args(shape))
    if shape.get("forbidden"):
        nbytes += float(pods * groups)
    if shape.get("score"):
        nbytes += 4.0 * pods * groups
    if shape.get("exclusive"):
        nbytes += float(pods)
    return nbytes


def least_time(shape: dict, peaks: dict) -> tuple:
    """(seconds, "compute" | "memory") for one solve of `shape`."""
    compute = binpack_ops(shape) / peaks["bf16_flops_per_s"]
    memory = binpack_bytes(shape) / peaks["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
