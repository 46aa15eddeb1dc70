"""SimLab: the trace-driven fleet simulator (docs/simulator.md).

Pins the ISSUE 17 acceptance surface: kernel/device/mirror parity is
bitwise (the ops/simstep.py contract), replay is deterministic under
the seed, batched stepping equals the sequential loop, every registered
scenario survives a random fault schedule without blocking and recovers
its reactive fixed point, the searched policy beats the reactive
baseline on a seeded pinned episode, the live `simlab` algorithm honors
the never-block contract, the docs catalog table cannot drift from the
registry, and the published batched-vs-sequential speedup is guarded.
"""

import dataclasses
import json
import os
import time
from argparse import Namespace

import numpy as np
import pytest

from karpenter_tpu.metrics.registry import GaugeRegistry
from karpenter_tpu.ops import simstep as SK
from karpenter_tpu.simlab import (
    BatchedSimEnv,
    SimEnv,
    SimParams,
    catalog,
    catalog_text,
    get_scenario,
    register_scenario,
    scenarios,
    select_for,
)
from karpenter_tpu.simlab.builtin import make_trails
from karpenter_tpu.simlab.policy import (
    FROZEN_KNOBS,
    REACTIVE_KNOBS,
    ReactivePolicy,
    SearchTunedPolicy,
    search_tuned_policy,
)
from karpenter_tpu.simlab.registry import Scenario
from karpenter_tpu.solver.service import SolverService

_F32 = np.float32

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _svc() -> SolverService:
    """A private service per test: own gauge registry, fresh stats."""
    return SolverService(registry=GaugeRegistry())


def _scalars(p: SimParams) -> dict:
    return {
        "cap": _F32(p.cap),
        "hourly": _F32(p.hourly),
        "step_limit": _F32(p.step_limit),
        "min_replicas": _F32(p.min_replicas),
        "max_replicas": _F32(p.max_replicas),
    }


def _batched_inputs(seeds, knobs, ticks=32, rows=4):
    """Batched SimRolloutInputs over independently-seeded episodes with
    every trail kind exercised (diurnal demand, price spikes, faults)."""
    trails = [
        make_trails(
            s, ticks=ticks, rows=rows, diurnal=True, amplitude=40.0,
            price_spike=1.5, fault_probability=0.2,
        )
        for s in seeds
    ]
    return SK.SimRolloutInputs(
        replicas0=np.stack([t.replicas0 for t in trails]),
        streak0=np.zeros((len(trails), rows), _F32),
        demand=np.stack([t.demand for t in trails]),
        forecast=np.stack([t.forecast for t in trails]),
        price=np.stack([t.price for t in trails]),
        fault=np.stack([t.fault for t in trails]),
        knobs=np.broadcast_to(
            np.asarray(knobs, _F32), (len(trails), SK.KNOBS)
        ).copy(),
        **_scalars(SimParams()),
    )


def _assert_rollout_equal(a, b):
    """Bitwise equality across every SimRolloutOutputs field."""
    for name in ("replicas", "streak", "violation", "cost", "backlog",
                 "target"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a, name)), np.asarray(getattr(b, name)),
            err_msg=f"rollout field {name} diverged",
        )


class TestKernelParity:
    """ops/simstep.py: jit == numpy mirror == vmapped, bitwise."""

    def test_step_jit_matches_numpy_bitwise(self):
        trails = make_trails(
            1, ticks=8, rows=6, spike=30.0, price_spike=2.0,
            fault_probability=0.5,
        )
        for t in range(4):
            inputs = SK.SimStepInputs(
                replicas=trails.replicas0,
                target=trails.demand[t] / _F32(2.0),
                demand=trails.demand[t],
                price=np.asarray(trails.price[t]),
                fault=np.asarray(trails.fault[t]),
                **_scalars(SimParams()),
            )
            dev = SK.sim_step_jit(inputs)
            host = SK.sim_step_numpy(inputs)
            for name in ("replicas", "violation", "cost", "backlog"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(dev, name)),
                    np.asarray(getattr(host, name)),
                    err_msg=f"tick {t} field {name} diverged",
                )

    def test_batched_step_matches_numpy_bitwise(self):
        trails = [make_trails(10 + i, ticks=4, rows=5) for i in range(3)]
        inputs = SK.SimStepInputs(
            replicas=np.stack([t.replicas0 for t in trails]),
            target=np.stack([t.demand[0] for t in trails]),
            demand=np.stack([t.demand[0] for t in trails]),
            price=np.stack([t.price[0] for t in trails]),
            fault=np.stack([t.fault[0] for t in trails]),
            **_scalars(SimParams()),
        )
        dev = SK.sim_step_jit(inputs)
        host = SK.sim_step_numpy(inputs)
        for name in ("replicas", "violation", "cost", "backlog"):
            np.testing.assert_array_equal(
                np.asarray(getattr(dev, name)),
                np.asarray(getattr(host, name)),
            )

    def test_vmapped_rollout_matches_per_cluster_jit_and_numpy(self):
        """The batched == sequential property pin, on DEVICE and on the
        mirror: one vmapped program over B clusters is bitwise the
        per-cluster scan loop and the numpy reference."""
        inputs = _batched_inputs(range(20, 24), FROZEN_KNOBS)
        batched = SK.sim_rollout_vmapped(inputs)
        host = SK.sim_rollout_numpy(inputs)
        _assert_rollout_equal(batched, host)
        for b in range(4):
            solo = SK.sim_rollout_jit(SK._cluster_slice(inputs, b))
            for name in ("replicas", "violation", "cost", "backlog",
                         "target"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(batched, name))[b],
                    np.asarray(getattr(solo, name)),
                    err_msg=f"cluster {b} field {name} diverged",
                )


class TestServiceSeam:
    """SolverService.sim_step/sim_rollout: one batched dispatch, the
    never-block mirror, and honest dispatch accounting."""

    def test_one_batched_dispatch_vs_b_sequential(self):
        svc = _svc()
        inputs = _batched_inputs(range(30, 34), FROZEN_KNOBS)
        batched = svc.sim_rollout(inputs, backend="xla")
        assert svc.stats.sim_calls == 1
        assert svc.stats.sim_dispatches == 1
        assert svc.stats.sim_mirror_serves == 0
        for b in range(4):
            solo = svc.sim_rollout(
                SK._cluster_slice(inputs, b), backend="xla"
            )
            np.testing.assert_array_equal(
                np.asarray(batched.replicas)[b], np.asarray(solo.replicas)
            )
        # the sequential loop paid B more dispatches for the same bits
        assert svc.stats.sim_calls == 5
        assert svc.stats.sim_dispatches == 5

    def test_device_fault_serves_bit_identical_mirror(self):
        """NEVER-BLOCK: a device failure at the `simlab.step` injection
        point serves the numpy mirror — same bits, no exception."""
        from karpenter_tpu.faults.registry import injected_faults

        inputs = _batched_inputs(range(40, 43), REACTIVE_KNOBS)
        svc = _svc()
        with injected_faults(seed=5) as reg:
            reg.plan("simlab.step", mode="error", probability=1.0)
            out = svc.sim_rollout(inputs, backend="xla")
        assert svc.stats.sim_mirror_serves == 1
        assert svc.stats.sim_dispatches == 0
        _assert_rollout_equal(out, SK.sim_rollout_numpy(inputs))


class TestSimEnv:
    def test_replay_twice_is_identical(self):
        env = SimEnv(get_scenario("forecast").trails, seed=3)
        first = env.run()
        second = env.run()
        assert first["reward"] == second["reward"]
        assert first["violation_ticks"] == second["violation_ticks"]
        assert first["hourly_cost"] == second["hourly_cost"]
        np.testing.assert_array_equal(
            first["final_replicas"], second["final_replicas"]
        )

    def test_distinct_seeds_draw_distinct_episodes(self):
        env = SimEnv(get_scenario("forecast").trails, seed=3)
        a = env.run()
        env.reset(seed=4)
        b = env.run(reset=False)
        assert a["reward"] != b["reward"]

    def test_unusable_actions_fall_back_to_reactive(self):
        env = SimEnv(get_scenario("cost").trails, seed=1)
        _obs, _r, _d, info = env.step(np.zeros(3, _F32))  # wrong shape
        assert info["reactive_fallback"]
        nan = np.full(env.trails.rows, np.nan, _F32)
        _obs, _r, _d, info = env.step(nan)
        assert info["reactive_fallback"]
        _obs, _r, _d, info = env.step(None)  # reactive BY CHOICE
        assert not info["reactive_fallback"]

    def test_step_after_done_raises(self):
        env = SimEnv(
            lambda seed: make_trails(seed, ticks=2, rows=2), seed=0
        )
        env.run()
        with pytest.raises(RuntimeError, match="done"):
            env.step(None)


class TestBatchedMatchesSequential:
    def test_rollout_equals_sequential_gym_loops(self):
        """The batched vmapped rollout and B sequential gym loops under
        the host SearchTunedPolicy tell the same story: same final
        replicas bitwise, same composite rewards (the host loop sums
        per-tick in a different order, hence approx not bitwise)."""
        trails_fn = get_scenario("forecast").trails
        batched = BatchedSimEnv(trails_fn, clusters=3, seed=3)
        out = batched.rollout(FROZEN_KNOBS)
        for i in range(3):
            env = SimEnv(trails_fn, seed=3 + i)
            run = env.run(SearchTunedPolicy(FROZEN_KNOBS))
            np.testing.assert_array_equal(
                out["final_replicas"][i], run["final_replicas"]
            )
            assert run["reward"] == pytest.approx(
                float(out["rewards"][i]), rel=1e-9
            )
            assert run["policy_faults"] == 0

    def test_reactive_knobs_are_the_reactive_baseline(self):
        """knobs (0,0,0) IS the reactive policy — the property that lets
        every comparison share one compiled program."""
        trails_fn = get_scenario("cost").trails
        kernel = SimEnv(trails_fn, seed=2).run(
            SearchTunedPolicy(REACTIVE_KNOBS)
        )
        reactive = SimEnv(trails_fn, seed=2).run(ReactivePolicy())
        assert kernel["reward"] == reactive["reward"]
        np.testing.assert_array_equal(
            kernel["final_replicas"], reactive["final_replicas"]
        )


class _FlakyPolicy:
    """Raises some ticks, emits poison some ticks — the fuzz adversary;
    the env must degrade those ticks to reactive and keep stepping."""

    def __init__(self):
        self._t = 0

    def reset(self):
        self._t = 0

    def act(self, obs):
        self._t += 1
        if self._t % 5 == 1:
            raise RuntimeError("injected policy fault")
        if self._t % 5 == 3:
            return np.full_like(obs["replicas"], np.nan)
        return None  # reactive by choice


class TestNeverBlockFuzz:
    def test_every_scenario_survives_random_faults_and_recovers(self):
        """Satellite (c): every registered scenario, stepped end to end
        under a RANDOM fault schedule and a misbehaving policy — no
        exception escapes, and once faults clear (the trail generators'
        fault-free constant tail) the fleet recovers the reactive fixed
        point ceil(tail demand / cap)."""
        for fuzz_seed, (name, sc) in enumerate(scenarios().items()):
            assert sc.trails is not None, f"{name} has no trails"

            def fuzzed(seed, sc=sc, fuzz_seed=fuzz_seed):
                trails = sc.trails(seed)
                rng = np.random.default_rng(1000 + fuzz_seed)
                tail = max(1, trails.ticks // 4)
                fault = (rng.random(trails.ticks) < 0.3).astype(_F32)
                fault[trails.ticks - tail:] = 0.0
                return dataclasses.replace(trails, fault=fault)

            env = SimEnv(fuzzed, params=sc.params, seed=11)
            run = env.run(_FlakyPolicy())
            assert run["policy_faults"] > 0, name
            assert run["reactive_fallbacks"] > 0, name
            p = sc.params
            expected = np.clip(
                np.ceil(env.trails.demand[-1] / _F32(p.cap)),
                _F32(p.min_replicas), _F32(p.max_replicas),
            ).astype(_F32)
            np.testing.assert_array_equal(
                run["final_replicas"], expected,
                err_msg=f"{name} did not recover its reactive fixed "
                f"point after the fault tail cleared",
            )


class TestPolicySearch:
    def test_search_beats_reactive_pinned(self):
        """Acceptance: SearchTunedPolicy beats the reactive baseline on
        the forecast scenario's composite reward — seeded, with the
        winning knob vector pinned."""
        result = search_tuned_policy(
            get_scenario("forecast").trails, seed=3
        )
        assert tuple(float(k) for k in result.knobs) == (1.0, 0.0, 4.0)
        assert result.margin > 0
        assert result.reward > result.baseline_reward
        assert result.dispatches == 2  # grid round + refinement round
        assert result.candidates == len(result.rewards)
        assert tuple(float(k) for k in REACTIVE_KNOBS) in result.rewards

    def test_baseline_reward_is_the_reactive_gym_reward(self):
        trails_fn = get_scenario("forecast").trails
        result = search_tuned_policy(trails_fn, seed=3, refine=False)
        reactive = SimEnv(trails_fn, seed=3).run(ReactivePolicy())
        assert result.baseline_reward == pytest.approx(
            reactive["reward"], rel=1e-9
        )

    def test_winner_replays_its_searched_score_in_the_gym_loop(self):
        """The host SearchTunedPolicy runs the SAME f32 math the search
        scored in-kernel, so the frozen winner keeps its score."""
        trails_fn = get_scenario("forecast").trails
        result = search_tuned_policy(trails_fn, seed=3)
        run = SimEnv(trails_fn, seed=3).run(result.policy())
        assert run["reward"] == pytest.approx(result.reward, rel=1e-9)

    def test_broken_policy_degrades_to_the_reactive_episode(self):
        class Boom:
            def reset(self):
                pass

            def act(self, obs):
                raise RuntimeError("always broken")

        trails_fn = get_scenario("cost").trails
        env = SimEnv(trails_fn, seed=1)
        broken = env.run(Boom())
        reactive = env.run(None)
        assert broken["policy_faults"] == env.trails.ticks
        assert broken["reward"] == reactive["reward"]


class TestScenarioRegistry:
    EXPECTED = (
        "trace", "constraints", "eventloop", "multitenant",
        "poolgroups", "cost", "forecast", "restart-storm", "failover",
        "preempt", "consolidate", "what-if", "karpenter",
    )

    @staticmethod
    def _args(**over):
        base = dict(
            trace_export=None, constraints=False, eventloop=False,
            multitenant=False, poolgroups=False, cost=False,
            forecast=False, restart_storm=False, failover=False,
            preempt=False, consolidate=False, what_if=None,
            sim_seed=None,
        )
        base.update(over)
        return Namespace(**base)

    def test_catalog_names_and_order(self):
        assert tuple(scenarios()) == self.EXPECTED

    def test_selection_precedence_matches_the_old_elif_chain(self):
        assert select_for(self._args()).name == "karpenter"
        assert select_for(self._args(constraints=True)).name == "constraints"
        # lower order wins when several flags are set
        assert select_for(
            self._args(constraints=True, cost=True)
        ).name == "constraints"
        # --trace-export combines with other worlds instead of winning
        assert select_for(
            self._args(trace_export="t.jsonl", cost=True)
        ).name == "cost"
        assert select_for(
            self._args(trace_export="t.jsonl")
        ).name == "trace"

    def test_duplicate_registration_rejected(self):
        with pytest.raises(ValueError, match="already registered"):
            register_scenario(Scenario(
                name="cost", description="dup", flags="--cost",
                order=999, select=lambda a: False,
                run=lambda a, s: 0,
            ))

    def test_unknown_scenario_lists_the_known(self):
        with pytest.raises(KeyError, match="registered:"):
            get_scenario("nope")

    def test_catalog_text_mentions_every_scenario(self):
        text = catalog_text()
        for name in self.EXPECTED:
            assert name in text
        assert "--sim-seed" in text


class TestDocDrift:
    """docs/simulator.md catalog table <-> registry, two directions —
    the metrics-lint discipline (tests/test_metrics.py) applied to the
    scenario catalog."""

    @staticmethod
    def _doc_rows():
        import re

        text = open(os.path.join(REPO_ROOT, "docs", "simulator.md")).read()
        section = text.split("## Scenario registry", 1)
        assert len(section) == 2, (
            "docs/simulator.md must carry the 'Scenario registry' section"
        )
        body = section[1].split("\n## ", 1)[0]
        rows = {}
        for match in re.finditer(
            r"^\| `([a-z-]+)` \| ([^|]+) \| ([^|]+) \| ([^|]+) \|",
            body, re.MULTILINE,
        ):
            rows[match.group(1)] = (
                match.group(2).strip().strip("`"),
                match.group(3).strip().strip("`"),
            )
        assert rows, "the scenario catalog table parsed empty"
        return rows

    def test_every_registered_scenario_is_documented(self):
        documented = set(self._doc_rows())
        missing = set(scenarios()) - documented
        assert not missing, (
            f"registered but missing from the docs/simulator.md catalog "
            f"table: {sorted(missing)}"
        )

    def test_every_documented_scenario_is_registered(self):
        stale = set(self._doc_rows()) - set(scenarios())
        assert not stale, (
            f"documented in docs/simulator.md but not registered: "
            f"{sorted(stale)}"
        )

    def test_flags_and_seededness_agree(self):
        rows = self._doc_rows()
        for name, _desc, flags, seeded in catalog():
            doc_flags, doc_seeded = rows[name]
            assert doc_flags == flags, (
                f"{name}: docs say flags {doc_flags!r}, registry says "
                f"{flags!r}"
            )
            expected = "--sim-seed" if seeded else "fixed"
            assert doc_seeded == expected, (
                f"{name}: docs say {doc_seeded!r}, registry says "
                f"{expected!r}"
            )


class TestCLI:
    def test_simulate_list_prints_the_catalog(self, tmp_path, capsys):
        from karpenter_tpu.__main__ import main

        rc = main([
            "--simulate", "--list",
            "--data-dir", str(tmp_path / "s"), "--no-leader-elect",
        ])
        assert rc == 0
        out = capsys.readouterr().out
        for name in TestScenarioRegistry.EXPECTED:
            assert name in out

    def test_default_seed_digests_pinned_and_sim_seed_threads(
        self, tmp_path, capsys
    ):
        """Satellite (a): the default seed reproduces the pre-registry
        CLI byte-identically (the pinned constraints digests), and
        --sim-seed actually reaches the world's RNG streams."""
        from karpenter_tpu.__main__ import main

        common = ["--data-dir", str(tmp_path / "s"), "--no-leader-elect"]
        rc = main(["--simulate", "--constraints"] + common)
        assert rc == 0
        default = json.loads(capsys.readouterr().out)
        assert default["digests"] == {
            "before": 1761739094,
            "after": 2968639679,
        }
        assert default["dead_zone"] == "z3"

        rc = main(
            ["--simulate", "--constraints", "--sim-seed", "8"] + common
        )
        assert rc == 0
        reseeded = json.loads(capsys.readouterr().out)
        assert reseeded["dead_zone"] == "z2"  # seed 8 kills a new zone
        assert reseeded["digests"] != default["digests"]


class TestSimlabAlgorithm:
    """The live hook: the frozen tuned policy as a registered algorithm
    behind the never-block contract."""

    @staticmethod
    def _metric(value, at, target=4.0):
        from karpenter_tpu.api.horizontalautoscaler import AVERAGE_VALUE
        from karpenter_tpu.autoscaler.algorithms import Metric

        return Metric(
            value=value, target_type=AVERAGE_VALUE, target_value=target,
            name="qps", owner=("default", "ha"), at=at,
        )

    def test_registered(self):
        from karpenter_tpu.autoscaler.algorithms import known_algorithms

        assert "simlab" in known_algorithms()

    def test_first_tick_is_plain_proportional(self):
        from karpenter_tpu.autoscaler.algorithms.simlab_policy import (
            SimlabPolicy,
        )

        algo = SimlabPolicy()
        assert algo.get_desired_replicas(self._metric(16.0, at=1.0), 4) == 4

    def test_ramp_scales_to_the_projection(self):
        """blend floor 1.0: a 16 -> 24 ramp projects to 32, so the
        desired count provisions the ramp ahead of the data."""
        from karpenter_tpu.autoscaler.algorithms.simlab_policy import (
            SimlabPolicy,
        )

        algo = SimlabPolicy()
        assert algo.get_desired_replicas(self._metric(16.0, at=1.0), 4) == 4
        assert algo.get_desired_replicas(self._metric(24.0, at=2.0), 4) == 8

    def test_scale_down_held_for_the_stabilization_window(self):
        from karpenter_tpu.autoscaler.algorithms.simlab_policy import (
            SimlabPolicy,
        )

        algo = SimlabPolicy()  # FROZEN_KNOBS: stab_window 2
        assert algo.get_desired_replicas(self._metric(32.0, at=1.0), 8) == 8
        # demand collapses: held for two ticks, released on the third
        assert algo.get_desired_replicas(self._metric(4.0, at=2.0), 8) == 8
        assert algo.get_desired_replicas(self._metric(4.0, at=3.0), 8) == 8
        assert algo.get_desired_replicas(self._metric(4.0, at=4.0), 8) == 1

    def test_scale_up_is_never_held(self):
        from karpenter_tpu.autoscaler.algorithms.simlab_policy import (
            SimlabPolicy,
        )

        algo = SimlabPolicy(knobs=[0.0, 0.0, 8.0])  # window only
        assert algo.get_desired_replicas(self._metric(4.0, at=1.0), 1) == 1
        assert algo.get_desired_replicas(self._metric(32.0, at=2.0), 1) == 8

    def test_poisoned_metric_never_blocks(self):
        """NaN reaches both the tuned path and the reactive fallback —
        the algorithm holds the fleet instead of raising."""
        from karpenter_tpu.autoscaler.algorithms.simlab_policy import (
            SimlabPolicy,
        )

        algo = SimlabPolicy()
        assert algo.get_desired_replicas(
            self._metric(float("nan"), at=1.0), 6
        ) == 6

    def test_clock_backwards_does_not_project(self):
        from karpenter_tpu.autoscaler.algorithms.simlab_policy import (
            SimlabPolicy,
        )

        algo = SimlabPolicy()
        assert algo.get_desired_replicas(self._metric(16.0, at=9.0), 4) == 4
        # an older sample must not become a projection base
        assert algo.get_desired_replicas(self._metric(24.0, at=5.0), 4) == 6


class TestLabels:
    class _FakeLedger:
        def __init__(self, records):
            self._records = records

        def query(self, kind=None, tenant=None, limit=None):
            return list(self._records)

    def test_stage_index_is_stable(self):
        from karpenter_tpu.observability.provenance import STAGES
        from karpenter_tpu.simlab import stage_index

        for i, stage in enumerate(STAGES):
            assert stage_index(stage) == i
        assert stage_index("unknown") == -1
        assert stage_index(None) == -1

    def test_label_stream_reshapes_and_nan_pads(self):
        from karpenter_tpu.observability.provenance import STAGES
        from karpenter_tpu.simlab import label_stream
        from karpenter_tpu.simlab.labels import FEATURE_NAMES

        ledger = self._FakeLedger([{
            "prev_replicas": 3, "base_desired": 5,
            "forecast_value": None, "forecast_skill": 0.9,
            "cost_hourly": 1.5, "cost_risk": None,
            "observed": [7.0],
            "final_desired": 4, "winning_stage": STAGES[0],
            "kind": "ha", "tenant": "blue", "name": "web",
            "group": "tpu",
        }])
        rows = label_stream(ledger)
        assert len(rows) == 1
        row = rows[0]
        assert len(row["features"]) == len(FEATURE_NAMES)
        assert row["features"][0] == 3.0
        assert np.isnan(row["features"][2])  # None forecast -> NaN
        assert row["features"][6] == 7.0  # observed_0
        assert np.isnan(row["features"][7])  # observed_1 padded
        assert row["label_desired"] == 4.0
        assert row["label_stage"] == 0
        assert row["tenant"] == "blue"


class TestRegressionGuard:
    def test_batched_beats_sequential_live(self):
        """Non-slow live guard: ONE vmapped dispatch must beat the
        per-cluster loop (generously; no cell measures SimLab on the
        chip)."""
        svc = _svc()
        inputs = _batched_inputs(range(16), FROZEN_KNOBS, ticks=64,
                                 rows=8)
        solos = [SK._cluster_slice(inputs, b) for b in range(16)]
        svc.sim_rollout(inputs, backend="xla")  # warm the vmapped jit
        svc.sim_rollout(solos[0], backend="xla")  # warm the solo jit
        assert svc.stats.sim_mirror_serves == 0

        best_batched = min(
            self._timed(lambda: svc.sim_rollout(inputs, backend="xla"))
            for _ in range(3)
        )
        best_sequential = min(
            self._timed(lambda: [
                svc.sim_rollout(s, backend="xla") for s in solos
            ])
            for _ in range(3)
        )
        assert best_batched * 2 < best_sequential, (
            f"batched {best_batched * 1e3:.3f}ms vs sequential "
            f"{best_sequential * 1e3:.3f}ms"
        )

    @staticmethod
    def _timed(fn):
        start = time.perf_counter()
        fn()
        return time.perf_counter() - start
