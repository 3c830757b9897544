"""Load-generator subsystem (ISSUE 8): the shared stats helper, the
deterministic schedule builder, the report invariant checks, and one
CI-scale end-to-end run through the real CLI against the self-hosted
server. The serving-side fairness/preemption invariants live in
tests/test_fair_sched.py; this file owns the harness itself."""

import dataclasses
import json

import pytest

from distributed_llama_tpu import stats
from distributed_llama_tpu.loadgen import report as rep
from distributed_llama_tpu.loadgen import workload as wl
from distributed_llama_tpu.loadgen.runner import OUTCOMES, RequestResult


# ----------------------------------------------------------------------
# stats.py — the ONE percentile estimator behind loadgen
# ----------------------------------------------------------------------


class TestStats:
    def test_percentile_interpolates_between_ranks(self):
        xs = [0.0, 10.0]
        assert stats.percentile(xs, 50) == 5.0
        assert stats.percentile(xs, 90) == 9.0
        assert stats.percentile(xs, 0) == 0.0
        assert stats.percentile(xs, 100) == 10.0

    def test_percentile_p99_of_hundred(self):
        xs = list(range(100))  # p99 index = 0.99 * 99 = 98.01
        assert stats.percentile(xs, 99) == pytest.approx(98.01)

    def test_empty_and_bad_q_raise(self):
        # a missing sample set must surface at the call site, never read
        # as a flattering 0ms latency
        with pytest.raises(ValueError):
            stats.percentile([], 50)
        with pytest.raises(ValueError):
            stats.percentile([1.0], 101)

    def test_summarize_shape_and_empty(self):
        s = stats.summarize([1.0, 2.0, 3.0], unit="ms")
        assert s["n"] == 3 and s["p50"] == 2.0 and s["min"] == 1.0
        assert s["unit"] == "ms"
        assert set(s) >= {"n", "mean", "p50", "p90", "p99", "min", "max"}
        # an absent percentile must be distinguishable from a zero one
        assert stats.summarize([]) == {"n": 0}


# ----------------------------------------------------------------------
# workload.py — deterministic schedules
# ----------------------------------------------------------------------


def _two_tenant_workload(seed=7, n=64):
    return wl.Workload(
        seed=seed, n_requests=n, rate_rps=50.0,
        tenants=[
            wl.TenantLoad("gold", share=0.25, priority=5, deadline_ms=9000,
                          slo_ttft_ms=2000),
            wl.TenantLoad("free", share=0.75),
        ],
    )


class TestSchedule:
    def test_replay_is_byte_identical(self):
        w = _two_tenant_workload()
        a, b = wl.build_schedule(w), wl.build_schedule(w)
        assert wl.schedule_fingerprint(a) == wl.schedule_fingerprint(b)
        assert [r.body for r in a] == [r.body for r in b]
        assert wl.scheduled_counts(a) == wl.scheduled_counts(b)

    def test_seed_changes_schedule(self):
        a = wl.build_schedule(_two_tenant_workload(seed=1))
        b = wl.build_schedule(_two_tenant_workload(seed=2))
        assert wl.schedule_fingerprint(a) != wl.schedule_fingerprint(b)

    def test_spec_changes_fingerprint(self):
        w = _two_tenant_workload()
        a = wl.build_schedule(w)
        b = wl.build_schedule(dataclasses.replace(w, zipf_s=2.0))
        assert wl.schedule_fingerprint(a) != wl.schedule_fingerprint(b)

    def test_arrivals_monotonic_and_rate_shaped(self):
        for arrival in ("poisson", "uniform", "burst"):
            w = dataclasses.replace(_two_tenant_workload(), arrival=arrival)
            sched = wl.build_schedule(w)
            ats = [r.at_s for r in sched]
            assert ats == sorted(ats)
            assert ats[0] >= 0.0

    def test_burst_groups_back_to_back(self):
        w = dataclasses.replace(
            _two_tenant_workload(n=16), arrival="burst", burst_size=8,
            burst_period_s=1.0,
        )
        sched = wl.build_schedule(w)
        # two bursts of 8: intra-burst spacing is 1ms, bursts 1s apart
        assert sched[7].at_s < 0.5 < sched[8].at_s

    def test_bodies_carry_tenant_fields(self):
        sched = wl.build_schedule(_two_tenant_workload())
        gold = [r for r in sched if r.tenant == "gold"]
        free = [r for r in sched if r.tenant == "free"]
        assert gold and free  # both tenants drew arrivals at these shares
        for r in gold:
            assert r.body["tenant"] == "gold"
            assert r.body["priority"] == 5
            assert r.body["deadline_ms"] == 9000
            assert r.body["temperature"] == 0.0  # the consistency contract
        for r in free:
            assert "priority" not in r.body

    def test_zipf_prefix_popularity_is_skewed(self):
        sched = wl.build_schedule(
            dataclasses.replace(_two_tenant_workload(n=200), n_prefixes=4)
        )
        counts = {}
        for r in sched:
            counts[r.prefix_id] = counts.get(r.prefix_id, 0) + 1
        # Zipf(1.1) over 4 prefixes: rank 0 must dominate rank 3 clearly
        assert counts.get(0, 0) > counts.get(3, 0)

    def test_identical_bodies_share_body_key(self):
        sched = wl.build_schedule(_two_tenant_workload(n=128))
        by_key = {}
        for r in sched:
            by_key.setdefault(r.body_key, []).append(r.body)
        assert any(len(v) > 1 for v in by_key.values())  # repeats exist
        for bodies in by_key.values():
            assert all(b == bodies[0] for b in bodies)

    def test_parse_tenant_loads(self):
        ts = wl.parse_tenant_loads(
            "gold:share=0.3,priority=5,slo_ttft_ms=2000;free:share=0.7"
        )
        assert [t.name for t in ts] == ["gold", "free"]
        assert ts[0].priority == 5 and ts[0].slo_ttft_ms == 2000.0
        assert wl.parse_tenant_loads(None)[0].name == "default"
        for bad in ("a:share=1;a:share=2", "a:wat=1", ":share=1"):
            with pytest.raises(ValueError):
                wl.parse_tenant_loads(bad)

    def test_workload_validation(self):
        for kw in ({"arrival": "chaotic"}, {"n_requests": 0},
                   {"rate_rps": 0.0}, {"tenants": []}, {"n_prefixes": 0}):
            with pytest.raises(ValueError):
                wl.Workload(**kw)
        with pytest.raises(ValueError):
            wl.TenantLoad("x", share=-1.0)


# ----------------------------------------------------------------------
# report.py — aggregation and the invariant checks
# ----------------------------------------------------------------------


def _result(i, tenant="t", outcome="completed", ttft=50.0, e2e=200.0,
            content="hello", key="k0"):
    return RequestResult(
        index=i, tenant=tenant, at_s=0.0, body_key=key, prefix_id=0,
        outcome=outcome, status=200 if outcome == "completed" else 429,
        ttft_ms=ttft if outcome == "completed" else None,
        e2e_ms=e2e if outcome == "completed" else None,
        content=content if outcome == "completed" else "",
    )


class TestReport:
    def test_parse_prometheus_and_label_sums(self):
        text = (
            "# HELP x y\n"
            "dllama_tenant_admitted_total{tenant=\"a\"} 3\n"
            "dllama_tenant_admitted_total{tenant=\"b\"} 2\n"
            "dllama_preemptions_total 1\n"
            "garbage line\n"
        )
        m = rep.parse_prometheus(text)
        assert rep._sum_series(m, "dllama_tenant_admitted_total") == 5.0
        assert rep._sum_series(m, "dllama_preemptions_total") == 1.0
        d = rep.metric_deltas({}, m, names=("dllama_preemptions_total",))
        assert d == {"dllama_preemptions_total": 1.0}

    def test_client_server_skew_per_tenant(self):
        # ISSUE 16: client-measured E2E vs the server's stage attribution
        before = rep.parse_prometheus(
            'dllama_request_stage_seconds_sum{stage="queue",tenant="a"} 0.5\n'
        )
        after = rep.parse_prometheus(
            'dllama_request_stage_seconds_sum{stage="queue",tenant="a"} 0.6\n'
            'dllama_request_stage_seconds_sum{stage="decode",tenant="a"} 0.08\n'
            'dllama_request_stage_seconds_sum{stage="decode",tenant="b"} 0.1\n'
        )
        results = [
            _result(0, tenant="a", e2e=200.0),
            _result(1, tenant="a", outcome="rejected"),  # not counted
            _result(2, tenant="b", e2e=100.0),
        ]
        skew = rep.client_server_skew(results, before, after)
        a = skew["a"]
        assert a["completed"] == 1
        assert a["client_e2e_s"] == pytest.approx(0.2)
        assert a["server_attributed_s"] == pytest.approx(0.18)
        assert a["skew_per_request_ms"] == pytest.approx(20.0)
        assert skew["b"]["skew_s"] == pytest.approx(0.0)

    def test_expected_flight_gate(self):
        snap = {"replicas": {
            "0": [
                {"kind": "fault_fire", "site": "replica.crash"},
                {"kind": "failover", "victims": 2},
            ],
            "1": [{"kind": "fault_fire", "site": "batch.row"}],
        }, "dumps": []}
        ok = rep.check_expected_flight(
            snap, ["fault_fire:2", "fault_fire@replica.crash", "failover:1"]
        )
        assert ok["ok"] and not ok["violations"]
        bad = rep.check_expected_flight(snap, ["watchdog_stall:1"])
        assert not bad["ok"] and "watchdog_stall" in bad["violations"][0]
        # an unreachable /debug/flight is itself a violation
        gone = rep.check_expected_flight(None, ["failover:1"])
        assert not gone["ok"]

    def test_consistency_flags_diverged_survivors(self):
        ok = rep.check_consistency(
            [_result(0, content="abc"), _result(1, content="abc")]
        )
        assert ok["ok"] and ok["repeated_groups"] == 1
        bad = rep.check_consistency(
            [_result(0, content="abc"), _result(1, content="abX")]
        )
        assert not bad["ok"] and bad["violations"]

    def test_consistency_excludes_casualties(self):
        # a quarantined request is an EXPECTED casualty under chaos — its
        # empty content must not read as a divergence
        chk = rep.check_consistency(
            [_result(0, content="abc"), _result(1, outcome="error")]
        )
        assert chk["ok"]

    def test_fairness_catches_lost_requests_and_starvation(self):
        w = _two_tenant_workload(n=8)
        sched = wl.build_schedule(w)
        results = [
            _result(r.index, tenant=r.tenant, key=r.body_key) for r in sched
        ]
        good = rep.build_report(
            w, sched, results, wall_s=1.0, fingerprint="f",
            replay_verified=True,
        )
        assert good["checks"]["fairness"]["ok"]
        assert good["checks"]["consistency"]["ok"]
        # starve one tenant: all its arrivals 429 while the other completes
        starved = [
            _result(
                r.index, tenant=r.tenant, key=r.body_key,
                outcome="rejected_429" if r.tenant == "gold" else "completed",
            )
            for r in sched
        ]
        bad = rep.build_report(
            w, sched, starved, wall_s=1.0, fingerprint="f",
            replay_verified=True,
        )
        assert not bad["checks"]["fairness"]["ok"]
        assert any("starved" in v for v in bad["checks"]["fairness"]["violations"])

    def test_goodput_counts_slo_misses_against_scheduled(self):
        w = wl.Workload(
            seed=0, n_requests=4,
            tenants=[wl.TenantLoad("t", slo_ttft_ms=100.0)],
        )
        sched = wl.build_schedule(w)
        results = [
            _result(r.index, key=r.body_key, ttft=50.0 if r.index < 2 else 500.0)
            for r in sched
        ]
        report = rep.build_report(
            w, sched, results, wall_s=2.0, fingerprint="f",
            replay_verified=True,
        )
        t = report["tenants"]["t"]
        # 2 of 4 completions inside SLO: fraction is of SCHEDULED, and the
        # rate divides by wall time
        assert t["goodput_under_slo"] == 0.5
        assert t["goodput_rps"] == 1.0
        assert t["counts"]["completed"] == 4

    def test_isolation_bound(self):
        solo = [_result(i, tenant="g", ttft=10.0) for i in range(4)]
        near = [_result(i, tenant="g", ttft=30.0) for i in range(4)]
        far = [_result(i, tenant="g", ttft=5000.0) for i in range(4)]
        assert rep.check_isolation("g", solo, near, bound=10, slack_ms=0)["ok"]
        chk = rep.check_isolation("g", solo, far, bound=10, slack_ms=0)
        assert not chk["ok"] and chk["violations"]
        # no completed samples in a phase is itself a failure, not a pass
        assert not rep.check_isolation("g", [], near)["ok"]

    def test_failed_checks_flattens(self):
        report = {"checks": {
            "a": {"ok": True, "violations": []},
            "b": {"ok": False, "violations": ["boom"]},
        }}
        assert rep.failed_checks(report) == ["[b] boom"]

    def test_outcome_buckets_cover_classifier(self):
        from distributed_llama_tpu.loadgen.runner import _classify_status

        for status, expect in ((429, "rejected_429"), (503, "draining_503"),
                               (504, "deadline_504"), (500, "error")):
            assert _classify_status(status) == expect
            assert expect in OUTCOMES


# ----------------------------------------------------------------------
# End-to-end: the real CLI against the self-hosted server (CI scale)
# ----------------------------------------------------------------------


class TestEndToEnd:
    def test_cli_selfhost_produces_asserted_report(self, tmp_path, capsys):
        from distributed_llama_tpu import telemetry
        from distributed_llama_tpu.loadgen.__main__ import main

        out = tmp_path / "report.json"
        try:
            code = main([
                "--self-host", "--requests", "8", "--rate", "40",
                "--tenants", "gold:share=0.5,priority=5;free:share=0.5",
                "--admission-queue", "16", "--warmup", "1",
                "--parallel", "2", "--assert", "--out", str(out),
            ])
        finally:
            # self-host enables the process-global registry; leave the
            # suite the way we found it
            telemetry.disable()
            telemetry.reset()
        assert code == 0
        report = json.loads(out.read_text())
        assert report["schedule"]["replay_verified"] is True
        assert report["checks"]["fairness"]["ok"]
        assert report["checks"]["consistency"]["ok"]
        # per-tenant percentile summaries exist for every tenant that
        # completed work (the acceptance-criteria report shape)
        for name, t in report["tenants"].items():
            if t["counts"]["completed"]:
                assert t["ttft_ms"]["n"] == t["counts"]["completed"]
                assert {"p50", "p90", "p99"} <= set(t["ttft_ms"])
        assert report["server"] is not None
        assert report["aggregate"]["counts"]["completed"] >= 1
