"""Zero-retrace steady state (exec/capacity.py + router SLO feedback).

Two planes:

- pinned grow-only buckets: hysteresis locked through the REAL
  ``retrace.attribute`` path (oscillating batch sizes around a bucket
  boundary → capacity-bucket count flat after warmup), the grow-only
  red test (shrinking inputs never re-bucket downward), sustained
  overflow growth, and the pinning-off A/B;
- router as SLO feedback controller: decisions are pure functions of
  (fingerprint, observation table, SLO context) — the same inputs
  produce the same decision, the ``slo-feedback`` reason appears only
  under a p99 violation with the error budget burning, and results are
  bit-identical with the feedback path on vs off.
"""

import jax
import jax.numpy as jnp
import pytest

from sail_tpu import SparkSession, events, faults
from sail_tpu.columnar.batch import bucket_capacity, round_capacity
from sail_tpu.exec import capacity, retrace
from sail_tpu.exec import local as xl
from sail_tpu.exec import router
from sail_tpu.exec.local import clear_caches


@pytest.fixture(autouse=True)
def _reset():
    capacity.reload()
    retrace.clear()
    router.clear_observations()
    yield
    clear_caches()
    capacity.reload()
    retrace.clear()
    router.clear_observations()
    faults.reset()
    events.reload()


# ---------------------------------------------------------------------------
# the registry: pin / grow-only / hysteresis semantics
# ---------------------------------------------------------------------------

def test_first_observation_pins_at_rounded_bucket():
    key = ("stage", "pin-me")
    assert bucket_capacity(1000, key=key) == round_capacity(1000)
    snap = capacity.snapshot()
    assert snap["pinned_count"] == 1
    assert snap["grow_count"] == 0


def test_grow_only_shrinking_inputs_never_rebucket_downward():
    # the red test: once warmed at 1000 rows (bucket 1024), smaller
    # batches MUST keep the pinned capacity — per-call rounding would
    # hand back 640/128/8 and retrace the program each time
    key = ("stage", "grow-only")
    pinned = bucket_capacity(1000, key=key)
    for smaller in (600, 100, 1):
        assert bucket_capacity(smaller, key=key) == pinned, \
            f"{smaller} rows re-bucketed below the pin"
    assert capacity.snapshot()["grow_count"] == 0


def test_single_spike_does_not_ratchet_the_pin():
    key = ("stage", "spike")
    pinned = bucket_capacity(1000, key=key)
    # one large batch runs at a correct transient capacity...
    assert bucket_capacity(50_000, key=key) == round_capacity(50_000)
    # ...but the pin did not move: the next normal batch is unchanged
    assert bucket_capacity(900, key=key) == pinned
    assert capacity.snapshot()["grow_count"] == 0


def test_sustained_overflow_grows_the_pin():
    key = ("stage", "sustained")
    bucket_capacity(1000, key=key)
    streak = capacity.snapshot()["grow_streak"]
    for _ in range(streak):
        got = bucket_capacity(50_000, key=key)
        assert got == round_capacity(50_000)
    assert capacity.snapshot()["grow_count"] == 1
    # grown: smaller batches now hold the NEW pin (still grow-only)
    assert bucket_capacity(900, key=key) == round_capacity(50_000)


def test_oscillation_around_boundary_stays_on_one_capacity():
    # 900 and 1100 round to different buckets (1024 vs 1280): per-call
    # rounding alternates programs, the pin does not
    assert round_capacity(900) != round_capacity(1100)
    key = ("stage", "oscillate")
    first = bucket_capacity(1100, key=key)
    caps = {bucket_capacity(n, key=key)
            for n in (900, 1100, 901, 1099, 1024, 1025)}
    assert caps == {first}


def test_pinning_off_restores_per_call_rounding(monkeypatch):
    monkeypatch.setenv("SAIL_EXECUTION__CAPACITY__PINNING", "0")
    capacity.reload()
    key = ("stage", "off")
    assert bucket_capacity(1100, key=key) == round_capacity(1100)
    assert bucket_capacity(900, key=key) == round_capacity(900)
    assert capacity.snapshot()["pinned_count"] == 0


# ---------------------------------------------------------------------------
# hysteresis through the REAL retrace.attribute path
# ---------------------------------------------------------------------------

def _run_at(fn, key, rows, cols=4):
    cap = bucket_capacity(rows, key=key)
    fn(jnp.zeros((cap, cols)))


def test_oscillating_sizes_zero_capacity_bucket_retraces_after_warmup():
    key = ("op", "hysteresis")
    f = xl._compile_timed(jax.jit(lambda x: x * 2), key)
    # warmup: one compile at the pinned capacity
    _run_at(f, key, 1100)
    assert retrace.LEDGER.totals() == {"first-ever": 1}
    # steady state: sizes oscillate around the 1024/1280 boundary —
    # with the pin every call reuses the warmed program
    for rows in (900, 1100, 1024, 1025, 901, 1099) * 3:
        _run_at(f, key, rows)
    totals = retrace.LEDGER.totals()
    assert totals.get("capacity-bucket", 0) == 0, totals
    assert totals == {"first-ever": 1}


def test_pinning_off_oscillation_pays_capacity_bucket_retraces(
        monkeypatch):
    monkeypatch.setenv("SAIL_EXECUTION__CAPACITY__PINNING", "0")
    capacity.reload()
    key = ("op", "hysteresis-off")
    f = xl._compile_timed(jax.jit(lambda x: x * 3), key)
    _run_at(f, key, 1100)
    for rows in (900, 1100, 900, 1100):
        _run_at(f, key, rows)
    # the A/B control: per-call rounding crossed the boundary and the
    # ledger attributed the recompile to capacity-bucket churn
    assert retrace.LEDGER.totals().get("capacity-bucket", 0) >= 1


def test_bit_identical_results_pinning_on_vs_off(monkeypatch):
    def run():
        spark = SparkSession.builder.getOrCreate()
        df = spark.createDataFrame(
            [(i, i % 7, float(i) * 0.5) for i in range(777)],
            ["a", "b", "c"])
        df.createOrReplaceTempView("t_cap")
        return spark.sql(
            "select b, count(*), sum(a), avg(c) from t_cap "
            "group by b order by b").collect()

    on = run()
    clear_caches()
    monkeypatch.setenv("SAIL_EXECUTION__CAPACITY__PINNING", "0")
    capacity.reload()
    off = run()
    assert on == off


# ---------------------------------------------------------------------------
# router: the SLO feedback controller
# ---------------------------------------------------------------------------

def _fake_stage():
    from sail_tpu.plan import nodes as pn
    from sail_tpu.plan import stages as pst
    from sail_tpu.spec import data_type as dt
    schema = (pn.Field("a", dt.LongType()),)
    scan = pn.ScanExec(out_schema=schema, table_name="t",
                       format="memory")
    agg = pn.AggregateExec(input=scan, group_indices=(),
                           aggs=(pn.AggSpec(fn="count"),),
                           out_names=("cnt",))
    split = pst.split_stages(agg)
    return next(s for s in split.stages if s.kind == "aggregate")


def _violating_ctx():
    return {"tenant": "t1", "target_ms": 10.0, "objective": 0.99,
            "burn": 2.0, "min_runs": 8}


def test_decide_stage_slo_feedback_reroutes_native_to_xla():
    stage = _fake_stage()
    key = router.stage_obs_key(stage)
    # observed: compute-bound (compile share tiny) but p99 way over a
    # 10 ms target
    for _ in range(16):
        router.note_stage(key, compile_s=0.0001, exec_s=0.050)
    base = router.decide_stage(stage, native_ok=True)
    assert base.backend == "native"
    d = router.decide_stage(stage, native_ok=True,
                            slo_ctx=_violating_ctx())
    assert (d.backend, d.reason) == ("xla", "slo-feedback")
    # deterministic: identical inputs, identical decision
    d2 = router.decide_stage(stage, native_ok=True,
                             slo_ctx=_violating_ctx())
    assert d == d2


def test_decide_stage_no_feedback_without_burn_or_violation():
    stage = _fake_stage()
    key = router.stage_obs_key(stage)
    for _ in range(16):
        router.note_stage(key, compile_s=0.0001, exec_s=0.050)
    calm = {"tenant": "t1", "target_ms": 10.0, "objective": 0.99,
            "burn": 0.2, "min_runs": 8}        # budget not burning
    assert router.decide_stage(stage, native_ok=True,
                               slo_ctx=calm).reason == "cost-model"
    slow_target = {"tenant": "t1", "target_ms": 500.0,
                   "objective": 0.99, "burn": 5.0, "min_runs": 8}
    assert router.decide_stage(
        stage, native_ok=True,
        slo_ctx=slow_target).reason == "cost-model"  # p99 under target


def test_compile_bound_stage_keeps_native_under_slo_pressure():
    stage = _fake_stage()
    key = router.stage_obs_key(stage)
    for _ in range(16):
        router.note_stage(key, compile_s=0.040, exec_s=0.050)
    d = router.decide_stage(stage, native_ok=True,
                            slo_ctx=_violating_ctx())
    # native IS the fix for compile-dominated stages: feedback defers
    assert (d.backend, d.reason) == ("native", "compile-bound")


def test_decide_plan_slo_feedback_presplits_to_mesh():
    from sail_tpu.analysis import anomaly
    from sail_tpu.plan import stages as pst
    spark = SparkSession.builder.getOrCreate()
    df = spark.createDataFrame([(i,) for i in range(10)], ["a"])
    df.createOrReplaceTempView("t_slo_plan")
    q = spark.sql("select a from t_slo_plan where a > 1")
    plan = spark._resolve(q._plan)
    fp = pst.plan_fingerprint_hash(plan)
    assert fp
    anomaly.reset()
    try:
        # feed the latency baseline: every observation far over target
        for i in range(20):
            anomaly.BASELINES.observe(
                {"fingerprint": fp, "query_id": f"q{i}",
                 "total_ms": 5000.0}, [])
        base = router.decide_plan(plan, nparts=8)
        assert (base.backend, base.reason) == ("xla", "dispatch-bound")
        d = router.decide_plan(plan, nparts=8, slo_ctx=_violating_ctx())
        assert (d.backend, d.reason) == ("mesh", "slo-feedback")
        assert router.decide_plan(plan, nparts=8,
                                  slo_ctx=_violating_ctx()) == d
    finally:
        anomaly.reset()


def test_slo_context_reads_last_burn_evaluation(monkeypatch):
    from sail_tpu.analysis import anomaly
    monkeypatch.setenv("SAIL_SLO__ENABLED", "1")
    # no evaluation recorded → feedback stays inert
    anomaly.SLO_MONITOR.reset()
    assert router.slo_context(None) is None
    # a recorded evaluation makes the context available
    anomaly.SLO_MONITOR._last_rows = [
        {"tenant": "default", "window": "fast", "burn_rate": 3.0},
        {"tenant": "default", "window": "slow", "burn_rate": 1.5},
    ]
    try:
        ctx = router.slo_context(None)
        assert ctx is not None
        assert ctx["burn"] == 3.0 and ctx["tenant"] == "default"
    finally:
        anomaly.SLO_MONITOR.reset()


def test_slo_feedback_gate_off(monkeypatch):
    from sail_tpu.analysis import anomaly
    monkeypatch.setenv("SAIL_EXECUTION__BACKEND__SLO_FEEDBACK", "0")
    anomaly.SLO_MONITOR._last_rows = [
        {"tenant": "default", "window": "fast", "burn_rate": 3.0}]
    try:
        assert router.slo_context(None) is None
    finally:
        anomaly.SLO_MONITOR.reset()
