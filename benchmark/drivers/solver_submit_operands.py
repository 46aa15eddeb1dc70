"""solver_submit's closed loop with the optional per-pod operands of the
north-star solve on every call: required node affinity as a
`pod_group_forbidden` mask, preferred node affinity as a
`pod_group_score`, and one-per-node rows (hostname self-anti-affinity)
as `pod_exclusive`.

Traffic keys, besides solver_submit's: `affinity_share` (rows with
required node affinity), `affinity_prototypes` (the distinct affinity
shapes such rows take, each ruling out each group with
p=`prototype_forbid_p`), `preferred_share` (rows with the preferred
term) and `preferred_weight` (its score on the configuration's ssd
groups, odd g), `exclusive_share` (one-per-node rows).

The mask follows `bench.py:build_inputs` at BASELINE.json config 4
(copied, not imported): a row with required affinity takes one of the
prototype rows, drawn uniformly. The preferred term is the
configuration's `prefer disk=ssd weight 80` under its `disk` rule. A
redrawn row has every operand redrawn with it, so the check and the
control rebuild any call from the seed alone.

setup, window, close and check are solver_submit's, run with this
module's Stream in the place of its own. The window adds the padded
operand bytes the service counted and counts as failed any call served
by another kernel than the one the service resolves to (on the chip the
Mosaic kernel; a reroute to XLA is a failure here). Importing this
module registers its control (the plain reference in bfloat16) into
benchmark.control.SUBSTITUTE.
"""

from __future__ import annotations

import contextlib
import dataclasses
import sys

import numpy as np

from benchmark import control as _control
from benchmark.drivers import solver_submit as S

OPERANDS = ("forbidden", "score", "exclusive")


class Stream(S.Stream):
    """solver_submit's stream of calls, with the three operands."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        super().__init__(config, traffic, seed)
        self.shares = {k: traffic[k] for k in ("affinity_share", "preferred_share", "exclusive_share")}
        rng = S._rng(seed, 3)
        self.prototypes = (rng.random((traffic["affinity_prototypes"], self.groups))
                           < traffic["prototype_forbid_p"])
        ssd = np.arange(self.groups) % 2 == 1
        self.preferred = np.where(ssd, traffic["preferred_weight"], 0).astype(np.float32)
        self.forbidden = np.zeros((self.pods, self.groups), bool)
        self.score = np.zeros((self.pods, self.groups), np.float32)
        self.exclusive = np.zeros(self.pods, bool)
        self._draw(rng, np.arange(self.pods))

    def _draw(self, rng, rows) -> None:
        n = len(rows)
        affine = rng.random(n) < self.shares["affinity_share"]
        shape = rng.integers(0, len(self.prototypes), n)
        preferred = rng.random(n) < self.shares["preferred_share"]
        self.forbidden[rows] = self.prototypes[shape] & affine[:, None]
        self.score[rows] = np.where(preferred[:, None], self.preferred, np.float32(0))
        self.exclusive[rows] = rng.random(n) < self.shares["exclusive_share"]

    def advance(self) -> None:
        """solver_submit's redraw, then the same rows' operands."""
        super().advance()
        rows = S._rng(self.seed, 1, self.calls).choice(self.pods, self.redraw, replace=False)
        self._draw(S._rng(self.seed, 4, self.calls), rows)

    def operands(self) -> dict:
        return dict(super().operands(), forbidden=self.forbidden, score=self.score,
                    exclusive=self.exclusive)

    def inputs(self):
        return dataclasses.replace(
            super().inputs(),
            pod_group_forbidden=self.forbidden.copy(),
            pod_group_score=self.score.copy(),
            pod_exclusive=self.exclusive.copy(),
        )


@contextlib.contextmanager
def _with_stream():
    """solver_submit's functions, building this module's Stream."""
    plain = S.Stream
    S.Stream = Stream
    try:
        yield
    finally:
        S.Stream = plain


def setup(run):
    with _with_stream():
        return S.setup(run)


def _operand_bytes(service):
    """The service's padded operand bytes by operand, or None where the
    program keeps no such count."""
    counted = getattr(service.stats, "operand_bytes", None)
    return None if counted is None else dict(counted)


def window(state, run):
    service = state["service"]
    before = _operand_bytes(service)
    reroutes = service.stats.constraint_reroutes
    result = S.window(state, run)
    layers = result.layers
    after = _operand_bytes(service)
    if after is not None:
        layers["operand_bytes"] = {k: v - before.get(k, 0) for k, v in after.items()}
    layers["shape"].update(dict.fromkeys(OPERANDS, True))
    kernel = f"solve/{'xla' if run.platform == 'cpu' else 'pallas'}"
    off_path = sum(v for k, v in layers["served"].items()
                   if k != kernel and not k.endswith("/numpy"))  # numpy: counted already
    result.failed += off_path
    run.log(f"operand bytes {layers.get('operand_bytes')}; constraint reroutes "
            f"{service.stats.constraint_reroutes - reroutes}; off {kernel} {off_path}")
    return result


close = S.close


def check(state, run) -> list:
    with _with_stream():
        return S.check(state, run)


def substitute(state, run) -> None:
    """The control: each kept call's answer becomes the bfloat16
    reference's, as for the solve cell."""
    with _with_stream():
        _control.substitute_solve(state, run)


# `python -m benchmark.control` runs that module as __main__, a second
# copy with a table of its own: register into each copy there is
for _module in (_control, sys.modules.get("__main__")):
    if getattr(getattr(_module, "__spec__", None), "name", None) == _control.__name__:
        _module.SUBSTITUTE.setdefault("solver_submit_operands", substitute)
