"""Replica fleet tests (ISSUE 7): device planning, dispatch policies,
failure containment (one replica down -> siblings keep serving, hub Health
stays SERVING), replica-granular revival, and the capability surface.

Routing/containment tests run on plain numpy MicroBatchers (no mesh — the
fleet is mesh-agnostic below the planner); the planner tests use the
suite's simulated 8-device CPU backend (``multidevice`` marker)."""

import json
import threading
import time

import numpy as np
import pytest

from lumen_tpu.runtime import fleet as fleet_mod
from lumen_tpu.runtime.batcher import MicroBatcher
from lumen_tpu.runtime.fleet import (
    DOWN,
    SERVING,
    LeastLoadedPolicy,
    Replica,
    ReplicaSet,
    RoundRobinPolicy,
    batcher_name,
    build_fleet,
    each_batcher,
    largest_dividing,
    plan_replicas,
    register_policy,
    replicas_for,
    topology_extra,
)
from lumen_tpu.runtime.quarantine import QuarantineRegistry
from lumen_tpu.testing.faults import faults
from lumen_tpu.utils.deadline import PoisonInput, WatchdogTimeout


class FakeClock:
    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, s: float) -> None:
        self.t += s


def make_build(
    name: str,
    fail_rids: set | None = None,
    quarantine: QuarantineRegistry | None = None,
    watchdog_s: float = 0.0,
    builds: dict | None = None,
):
    """Batcher factory for a numpy fleet: doubles every row; replicas in
    ``fail_rids`` raise on every dispatch. ``builds`` counts factory calls
    per rid (revival proofs)."""

    def build(rid, mesh):  # noqa: ARG001 - meshless fleet
        if builds is not None:
            builds[rid] = builds.get(rid, 0) + 1

        def fn(tree, n, _rid=rid):
            if fail_rids and _rid in fail_rids:
                raise RuntimeError(f"replica {_rid} broken")
            return tree * 2

        return MicroBatcher(
            fn,
            max_batch=4,
            max_latency_ms=1.0,
            name=batcher_name(name, rid),
            quarantine=quarantine,
            watchdog_s=watchdog_s,
            replica=None if rid is None else f"r{rid}",
        ).start()

    return build


class TestKnobs:
    def test_default_is_one(self, monkeypatch):
        monkeypatch.delenv("LUMEN_REPLICAS", raising=False)
        monkeypatch.delenv("LUMEN_REPLICAS_CLIP", raising=False)
        assert replicas_for("clip") == 1

    def test_global_and_per_family_override(self, monkeypatch):
        monkeypatch.setenv("LUMEN_REPLICAS", "2")
        assert replicas_for("clip") == 2
        monkeypatch.setenv("LUMEN_REPLICAS_CLIP", "4")
        assert replicas_for("clip") == 4
        assert replicas_for("face") == 2  # global still governs siblings

    def test_max_and_malformed(self, monkeypatch):
        monkeypatch.setenv("LUMEN_REPLICAS", "max")
        assert replicas_for("clip") == -1
        monkeypatch.setenv("LUMEN_REPLICAS", "banana")
        assert replicas_for("clip") == 1

    def test_unknown_policy_degrades(self, monkeypatch):
        monkeypatch.setenv("LUMEN_REPLICA_POLICY", "coin_flip")
        assert fleet_mod.dispatch_policy_name() == "round_robin"
        monkeypatch.setenv("LUMEN_REPLICA_POLICY", "least_loaded")
        assert fleet_mod.dispatch_policy_name() == "least_loaded"

    def test_largest_dividing(self):
        assert largest_dividing(4, 8) == 4
        assert largest_dividing(3, 8) == 2
        assert largest_dividing(8, 4) == 4
        assert largest_dividing(5, 6) == 3
        assert largest_dividing(1, 7) == 1


@pytest.mark.multidevice
class TestPlan:
    def test_single_replica_is_pre_fleet_mesh(self, monkeypatch, multidevice):
        monkeypatch.delenv("LUMEN_REPLICAS", raising=False)
        plan = plan_replicas("clip")
        assert plan.replicas == 1 and len(plan.meshes) == 1
        assert dict(plan.meshes[0].shape) == {"data": 8}

    def test_four_replicas_two_devices_each(self, monkeypatch, multidevice):
        monkeypatch.setenv("LUMEN_REPLICAS_CLIP", "4")
        plan = plan_replicas("clip")
        assert plan.replicas == 4 and plan.devices_per_replica == 2
        assert all(dict(m.shape) == {"data": 2} for m in plan.meshes)
        # Disjoint slices: every device appears in exactly one replica.
        ids = [d.id for m in plan.meshes for d in m.devices.ravel()]
        assert sorted(ids) == sorted(set(ids)) and len(ids) == 8

    def test_nondividing_count_degrades(self, monkeypatch, multidevice):
        monkeypatch.setenv("LUMEN_REPLICAS_CLIP", "3")
        assert plan_replicas("clip").replicas == 2

    def test_oversubscribed_count_clamps_to_devices(self, monkeypatch, multidevice):
        # The ISSUE satellite example: LUMEN_REPLICAS=8 on a 4-chip host
        # serves 4 replicas instead of failing boot.
        import jax

        monkeypatch.setenv("LUMEN_REPLICAS_CLIP", "8")
        plan = plan_replicas("clip", devices=jax.local_devices()[:4])
        assert plan.replicas == 4 and plan.devices_per_replica == 1

    def test_tp_axes_stay_inside_replicas(self, monkeypatch, multidevice):
        monkeypatch.setenv("LUMEN_REPLICAS_CLIP", "max")
        plan = plan_replicas("clip", {"model": 2})
        assert plan.replicas == 4
        assert all(dict(m.shape) == {"model": 2, "data": 1} for m in plan.meshes)

    def test_wildcard_tp_axis_absorbs_the_slice(self, monkeypatch, multidevice):
        # {"model": -1} (TP over whatever is available) + replicas must not
        # produce a second -1 axis: the wildcard absorbs each slice.
        monkeypatch.setenv("LUMEN_REPLICAS_CLIP", "2")
        plan = plan_replicas("clip", {"model": -1})
        assert plan.replicas == 2
        assert all(dict(m.shape) == {"model": 4} for m in plan.meshes)


class TestPolicies:
    @staticmethod
    def _stub_replicas(loads):
        class StubBatcher:
            def __init__(self, load):
                self._load = load

            def load(self):
                return self._load

        return [Replica(i, None, StubBatcher(l)) for i, l in enumerate(loads)]

    def test_round_robin_cycles(self):
        live = self._stub_replicas([0, 0, 0])
        policy = RoundRobinPolicy()
        picks = [policy.pick(live).rid for _ in range(6)]
        assert sorted(picks[:3]) == [0, 1, 2] and picks[:3] == picks[3:]

    def test_least_loaded_picks_minimum(self):
        live = self._stub_replicas([5, 1, 3])
        assert LeastLoadedPolicy().pick(live).rid == 1

    def test_custom_policy_registry(self):
        class Last:
            name = "always_last"

            def pick(self, live):
                return live[-1]

        register_policy("always_last", Last)
        try:
            rs = ReplicaSet(
                "custom-pol", make_build("custom-pol"), [None] * 3,
                policy="always_last", revive_s=0,
            )
            try:
                rs(np.ones(1))
                assert rs.replicas[2].dispatches == 1
                assert rs.replicas[0].dispatches == rs.replicas[1].dispatches == 0
            finally:
                rs.close()
        finally:
            fleet_mod.POLICIES.pop("always_last", None)


class TestReplicaSet:
    def test_routes_and_returns_rows(self):
        rs = ReplicaSet("route", make_build("route"), [None] * 4, revive_s=0)
        try:
            outs = [rs(np.array([float(i)])) for i in range(12)]
            assert all(float(o[0]) == 2.0 * i for i, o in enumerate(outs))
            # Round-robin spreads the singles evenly.
            assert [r.dispatches for r in rs.replicas] == [3, 3, 3, 3]
            assert rs.states() == {f"r{i}": SERVING for i in range(4)}
        finally:
            rs.close()

    def test_quarantined_fingerprint_raises_without_failover(self):
        q = QuarantineRegistry(ttl_s=600)
        rs = ReplicaSet(
            "quar", make_build("quar", quarantine=q), [None] * 2, revive_s=0
        )
        try:
            q.add("bad-fp", "poisoned upstream")
            with pytest.raises(PoisonInput):
                rs.submit(np.ones(1), fingerprint="bad-fp")
            # A payload verdict is identical on every replica: no dispatch
            # was tried, no replica took the blame.
            assert all(r.streak == 0 and r.state == SERVING for r in rs.replicas)
        finally:
            rs.close()
            q.close()

    def test_queue_full_fails_over_to_sibling(self):
        release = threading.Event()

        def build(rid, mesh):  # noqa: ARG001
            def fn(tree, n, _rid=rid):
                if _rid == 0:
                    release.wait(5)
                return tree * 2

            return MicroBatcher(
                fn, max_batch=1, max_latency_ms=1.0, max_queue=1,
                name=batcher_name("qfull", rid),
            ).start()

        class PinFirst:
            name = "pin_first"

            def pick(self, live):
                return live[0]

        rs = ReplicaSet("qfull", build, [None] * 2, policy=PinFirst(), revive_s=0)
        try:
            # Saturate r0: one in the (blocked) dispatch, one queued.
            futs = [rs.submit(np.ones(1)) for _ in range(2)]
            deadline = time.monotonic() + 5
            while rs.replicas[0].batcher.load() < 2 and time.monotonic() < deadline:
                time.sleep(0.005)
            # r0 full -> the routed submit fails over to r1 and serves.
            out = rs(np.ones(1))
            assert float(out[0]) == 2.0
            assert rs.replicas[1].dispatches >= 1
            release.set()
            for f in futs:
                f.result(timeout=5)
        finally:
            release.set()
            rs.close()

    def test_all_replicas_down_raises_watchdog_timeout(self):
        rs = ReplicaSet(
            "alldown", make_build("alldown", fail_rids={0, 1}), [None] * 2,
            failures=1, revive_s=0,
        )
        try:
            for _ in range(4):
                with pytest.raises(RuntimeError):
                    rs(np.ones(1))
            assert rs.states() == {"r0": DOWN, "r1": DOWN}
            with pytest.raises(WatchdogTimeout, match="all 2 replicas down"):
                rs.submit(np.ones(1))
        finally:
            rs.close()


class TestContainment:
    def test_failure_streak_downs_only_the_broken_replica(self):
        rs = ReplicaSet(
            "contain", make_build("contain", fail_rids={1}), [None] * 4,
            failures=2, revive_s=0,
        )
        try:
            errors = 0
            for i in range(16):
                try:
                    out = rs(np.array([float(i)]))
                    assert float(out[0]) == 2.0 * i
                except RuntimeError:
                    errors += 1  # contained: only r1's callers fail
            states = rs.states()
            assert states["r1"] == DOWN
            assert all(s == SERVING for t, s in states.items() if t != "r1")
            assert 2 <= errors <= 4  # the streak, not the whole batch stream
            # Once down, the dispatcher never routes to r1 again.
            for i in range(12):
                assert float(rs(np.array([float(i)]))[0]) == 2.0 * i
        finally:
            rs.close()

    def test_one_failed_batch_counts_as_one_failure_event(self):
        def build(rid, mesh):  # noqa: ARG001
            def fn(tree, n):
                raise RuntimeError("device fault")

            return MicroBatcher(
                fn, max_batch=4, max_latency_ms=100.0, bisect_depth=0,
                name=batcher_name("onebatch", rid),
            ).start()

        rs = ReplicaSet("onebatch", build, [None], failures=3, revive_s=0)
        try:
            # Four callers coalesce into ONE batch; the batch fails and
            # settles all four futures with the SAME exception instance.
            futs = [rs.submit(np.ones(1)) for _ in range(4)]
            for f in futs:
                with pytest.raises(RuntimeError):
                    f.result(timeout=10)
            # One backend event, one streak tick — threshold 3 not tripped.
            assert rs.replicas[0].streak == 1
            assert rs.states() == {"r0": SERVING}
        finally:
            rs.close()

    def test_replica_states_string_is_rid_ordered_past_ten(self):
        rs = ReplicaSet(
            "wide", make_build("wide", fail_rids={10}), [None] * 12,
            failures=1, revive_s=0,
        )
        try:
            while rs.states()["r10"] == SERVING:
                try:
                    rs(np.ones(1))
                except RuntimeError:
                    pass
            extra = topology_extra(None, rs)
            states = extra["replica_states"].split(",")
            assert len(states) == 12
            assert states[10] == DOWN  # position i IS replica i
            assert all(s == SERVING for i, s in enumerate(states) if i != 10)
        finally:
            rs.close()

    def test_wedged_replica_contained_and_skipped(self):
        faults.configure("batch_hang", match="wedge-r1")
        rs = ReplicaSet(
            "wedge", make_build("wedge", watchdog_s=0.15), [None] * 3,
            failures=3, revive_s=0,
        )
        try:
            # Drive until some caller lands on r1 and its watchdog fires.
            failures = 0
            deadline = time.monotonic() + 20
            while rs.states()["r1"] == SERVING and time.monotonic() < deadline:
                try:
                    rs(np.ones(1), timeout=5)
                except WatchdogTimeout:
                    failures += 1
            assert rs.states()["r1"] == DOWN
            assert failures >= 1
            # Siblings keep serving; the wedge is invisible to new traffic.
            for _ in range(8):
                assert float(rs(np.ones(1))[0]) == 2.0
        finally:
            faults.reset()
            rs.close()

    def test_hub_health_stays_serving_with_one_replica_down(self):
        from lumen_tpu.serving import HubRouter
        from lumen_tpu.serving.base_service import BaseService
        from lumen_tpu.serving.registry import TaskDefinition, TaskRegistry

        rs = ReplicaSet(
            "hub-fleet", make_build("hub-fleet", fail_rids={1}), [None] * 2,
            failures=1, revive_s=0,
        )

        class FleetService(BaseService):
            def __init__(self):
                reg = TaskRegistry("fleet-svc")
                reg.register(TaskDefinition(name="fleet_task", handler=self._run))
                super().__init__(reg)

            def _run(self, payload, mime, meta):  # noqa: ARG002
                rs(np.ones(1))
                return b"ok", "text/plain", {}

            def capability(self):
                return self.registry.build_capability(
                    model_ids=[], runtime="none", extra=topology_extra(None, rs)
                )

            def replica_states(self):
                return {rs.name: rs.states()}

        svc = FleetService()
        router = HubRouter({"fleet": svc})
        try:
            # Break r1 (its caller eats the contained error).
            while rs.states()["r1"] == SERVING:
                try:
                    rs(np.ones(1))
                except RuntimeError:
                    pass

            trailing = {}

            class Ctx:
                def set_trailing_metadata(self, md):
                    trailing.update(dict(md))

                def abort(self, code, msg):
                    raise AssertionError(f"hub went unhealthy: {msg}")

            router.Health(None, Ctx())  # no abort = SERVING
            states = json.loads(trailing["lumen-replica-status"])
            assert states == {"fleet": {"hub-fleet": {"r0": "serving", "r1": "down"}}}
            statuses = json.loads(trailing["lumen-service-status"])
            assert statuses == {"fleet": "healthy"}
            # Capability extra carries the live layout for fleet clients.
            cap = next(iter(router.StreamCapabilities(None, None)))
            assert cap.extra["replicas"] == "2"
            assert cap.extra["replica_states"] == "serving,down"
            assert cap.extra["replica_policy"] == "round_robin"
        finally:
            rs.close()


class TestRevive:
    def test_due_respects_cooldown_with_fake_clock(self):
        clock = FakeClock()
        rs = ReplicaSet(
            "cooldown", make_build("cooldown", fail_rids={1}), [None] * 2,
            failures=1, revive_s=10.0, clock=clock,
        )
        try:
            with pytest.raises(RuntimeError):
                # Policy may pick r0 first; loop until r1 takes the hit.
                for _ in range(4):
                    rs(np.ones(1))
            assert rs.states()["r1"] == DOWN
            assert rs._due() == []  # cooldown not elapsed on the fake clock
            clock.advance(9.9)
            assert rs._due() == []
            clock.advance(0.2)
            assert [r.rid for r in rs._due()] == [1]
        finally:
            rs.close()

    def test_revive_swaps_only_the_dead_replica(self):
        builds: dict = {}
        fail = {1}
        rs = ReplicaSet(
            "swap", make_build("swap", fail_rids=fail, builds=builds),
            [None] * 3, failures=1, revive_s=0,
        )
        try:
            while rs.states()["r1"] == SERVING:
                try:
                    rs(np.ones(1))
                except RuntimeError:
                    pass
            siblings = {r.rid: r.batcher for r in rs.replicas if r.rid != 1}
            dead = rs.replicas[1].batcher
            fail.clear()  # the fault condition heals
            assert rs.revive(1)
            assert rs.states() == {f"r{i}": SERVING for i in range(3)}
            # Only the dead replica's batcher was rebuilt.
            assert rs.replicas[1].batcher is not dead
            for rid, b in siblings.items():
                assert rs.replicas[rid].batcher is b
            assert builds == {0: 1, 1: 2, 2: 1}
            # And it serves again.
            for i in range(6):
                assert float(rs(np.array([2.0]))[0]) == 4.0
        finally:
            rs.close()

    def test_revive_rejects_a_serving_replica(self):
        builds: dict = {}
        rs = ReplicaSet(
            "noheal", make_build("noheal", builds=builds), [None] * 2, revive_s=0
        )
        try:
            healthy = rs.replicas[0].batcher
            assert not rs.revive(0)  # only DOWN replicas get rebuilt
            assert rs.replicas[0].batcher is healthy
            assert rs.states() == {"r0": SERVING, "r1": SERVING}
            assert builds == {0: 1, 1: 1}
        finally:
            rs.close()

    def test_failed_revive_rearms_cooldown(self):
        clock = FakeClock()
        builds: dict = {}

        def build(rid, mesh):
            if builds.get(1, 0) >= 1 and rid == 1:
                builds[1] = builds.get(1, 0) + 1
                raise RuntimeError("rebuild exploded")
            return make_build("deadrev", fail_rids={1}, builds=builds)(rid, mesh)

        rs = ReplicaSet(
            "deadrev", build, [None] * 2, failures=1, revive_s=5.0, clock=clock
        )
        try:
            while rs.states()["r1"] == SERVING:
                try:
                    rs(np.ones(1))
                except RuntimeError:
                    pass
            assert not rs.revive(1)
            assert rs.states()["r1"] == DOWN
            assert rs._due() == []  # cooldown re-armed from the failure
            clock.advance(5.1)
            assert [r.rid for r in rs._due()] == [1]
        finally:
            rs.close()

    def test_background_revive_restores_service(self):
        builds: dict = {}
        fail = {0}
        rs = ReplicaSet(
            "autorev", make_build("autorev", fail_rids=fail, builds=builds),
            [None] * 2, failures=1, revive_s=0.05,
        )
        try:
            while rs.states()["r0"] == SERVING:
                try:
                    rs(np.ones(1))
                except RuntimeError:
                    pass
            fail.clear()
            deadline = time.monotonic() + 10
            while rs.states()["r0"] != SERVING and time.monotonic() < deadline:
                time.sleep(0.02)
            assert rs.states() == {"r0": SERVING, "r1": SERVING}
            assert builds[0] == 2
        finally:
            rs.close()


class TestHelpers:
    def test_batcher_name(self):
        assert batcher_name("clip-image", None) == "clip-image"
        assert batcher_name("clip-image", 2) == "clip-image-r2"

    def test_each_batcher_plain_and_fleet(self):
        b = MicroBatcher(lambda t, n: t, max_batch=2, name="solo").start()
        try:
            assert list(each_batcher(b)) == [b]
            assert list(each_batcher(None)) == []
        finally:
            b.close()
        rs = ReplicaSet("each", make_build("each"), [None] * 2, revive_s=0)
        try:
            assert len(list(each_batcher(rs))) == 2
        finally:
            rs.close()

    def test_build_fleet_single_replica_is_plain_batcher(self, monkeypatch, multidevice):
        monkeypatch.delenv("LUMEN_REPLICAS", raising=False)
        plan = plan_replicas("clip")
        built = build_fleet(plan, "plain", make_build("plain"))
        try:
            assert isinstance(built, MicroBatcher)
            assert built.name == "plain"  # no -rN suffix: gauges don't move
        finally:
            built.close()

    def test_topology_extra_without_fleet(self):
        extra = topology_extra(None)
        assert extra["replicas"] == "1"
        assert "device_count" in extra

    def test_replica_gauges_registered(self):
        from lumen_tpu.utils.metrics import metrics

        rs = ReplicaSet("gauged", make_build("gauged"), [None] * 2, revive_s=0)
        try:
            rs(np.ones(1))
            gauges = metrics.snapshot()["gauges"].get("replica:gauged")
            assert gauges is not None
            assert gauges["replicas"] == 2 and gauges["down"] == 0
            assert gauges["r0_state"] == 0 and "r0_dispatches" in gauges
        finally:
            rs.close()
        # .get: the snapshot has no "gauges" key when no provider is left
        # (this test alone in its process, or first in its worker)
        assert "replica:gauged" not in metrics.snapshot().get("gauges", {})

    def test_load_counts_queued_and_inflight(self):
        release = threading.Event()
        b = MicroBatcher(
            lambda t, n: (release.wait(5), t)[1], max_batch=1, name="loaded"
        ).start()
        try:
            assert b.load() == 0
            futs = [b.submit(np.ones(1)) for _ in range(3)]
            deadline = time.monotonic() + 5
            while b.load() < 3 and time.monotonic() < deadline:
                time.sleep(0.005)
            assert b.load() == 3
            release.set()
            for f in futs:
                f.result(timeout=5)
        finally:
            release.set()
            b.close()
