"""CPU tests of the benchmark: schedules, end-to-end arithmetic, work
functions, the trace reduction on a trace recorded on a TPU v5e, the
float32 reference against the program, and whole runs at a tiny size in
which the output check passes, its fp8 control does not, and a token
altered where it is produced fails the check.

Run from the repository root:  python -m pytest -q tests/bench
Nothing here describes or touches a TPU.
"""
from __future__ import annotations

import gzip
import json
import os
import statistics
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import check, load, run, stats, work  # noqa: E402
from bench import trace as trace_lib  # noqa: E402

# 0.6 s of qwen2.5-3b.prefill-backlog traced on a TPU v5e (gzip).
TRACE = os.path.join(ROOT, "bench", "testdata", "backlog.xplane.pb.gz")


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# schedules
# ---------------------------------------------------------------------------

def _traffic(name):
    with open(os.path.join(ROOT, "bench", "traffic", name + ".json")) as f:
        return json.load(f)


def test_same_seed_same_schedule():
    t = _traffic("chat")
    a = load.build(t, 2 ** 31 + 7, 30, 151936)
    b = load.build(t, 2 ** 31 + 7, 30, 151936)
    c = load.build(t, 2 ** 31 + 8, 30, 151936)
    shape = [(p.due, p.max_new, len(p.prompt)) for p in a]
    assert shape == [(p.due, p.max_new, len(p.prompt)) for p in b]
    assert all(np.array_equal(p.prompt, q.prompt) for p, q in zip(a, b))
    # another seed: the same gaps and lengths in another order, on other
    # tokens
    other = [(p.due, p.max_new, len(p.prompt)) for p in c]
    assert other != shape
    for k in (1, 2):
        assert sorted(x[k] for x in other) == sorted(x[k] for x in shape)
    assert not any(np.array_equal(p.prompt, q.prompt) for p, q in zip(a, c))


@pytest.mark.parametrize("mix", ["chat", "prefill-backlog"])
def test_prompts_lie_on_the_ladder(mix):
    t = _traffic(mix)
    rungs = set(load.ladder(t["prompt_tokens"]))
    assert len(rungs) == 16
    plan = load.build(t, 11, 30, 1000)
    assert {len(p.prompt) for p in plan} <= rungs
    lo, hi = t["answer_tokens"]["min"], t["answer_tokens"]["max"]
    assert all(lo <= p.max_new <= hi for p in plan)


def test_window_holds_the_same_work_for_every_seed():
    t = _traffic("chat")
    seen = set()
    for seed in (1, 2, 3 ** 20):
        win = [p for p in load.build(t, seed, 30, 1000) if p.in_window]
        seen.add((len(win), tuple(sorted(len(p.prompt) for p in win)),
                  tuple(sorted(p.max_new for p in win))))
        assert all(0 <= p.due < 30 for p in win)
    assert len(seen) == 1


def test_arrivals_keep_the_rate():
    t = dict(_traffic("chat"), rate_per_s=3.0, lead_in_s=0)
    plan = load.build(t, 5, 40, 1000)
    assert len(plan) == 120
    gaps = np.diff([p.due for p in plan])
    assert abs(gaps.mean() - 1 / 3.0) < 0.01
    # exponential: the standard deviation is about the mean
    assert 0.8 < gaps.std() / gaps.mean() < 1.2


@pytest.mark.parametrize("rate", [1.4, 1.55, 2.0])
def test_every_request_falls_due_inside_its_span(rate):
    t = dict(_traffic("chat"), rate_per_s=rate)
    for seed in (3, 2 ** 31 + 11):
        plan = load.build(t, seed, 50, 1000)
        win = [p.due for p in plan if p.in_window]
        lead = [p.due for p in plan if not p.in_window]
        assert len(win) == round(rate * 50) and all(0 <= d < 50 for d in win)
        assert all(-t["lead_in_s"] <= d < 0 for d in lead)


@pytest.mark.parametrize("seen, knee", [
    ({1.5: [(0, 130.0), (0, 135.0)], 1.75: [(0, 140.0), (0, 300.0)],
      2.0: [(0, 150.0), (0, 140.0)]}, 1.5),
    ({1.5: [(0, 130.0)], 1.75: [(0, 140.0)], 2.0: [(2, 150.0)]}, 1.75),
    ({1.5: [(0, 130.0)], 2.0: [(0, 250.0)]}, 2.0),
    ({1.5: [(1, 130.0)], 2.0: [(0, 140.0)]}, None),
])
def test_knee_is_sustained_on_every_seed(seen, knee):
    from bench import sweep
    assert sweep.knee_of(seen) == knee


def test_backlog_strata_repeat_the_lengths():
    t = _traffic("prefill-backlog")
    plan = load.build(t, 9, 30, 1000)
    assert len(plan) == t["backlog_requests"]
    assert all(p.due == -t["lead_in_s"] for p in plan)
    k = t["stratum"]
    first = sorted(len(p.prompt) for p in plan[:k])
    second = sorted(len(p.prompt) for p in plan[k:2 * k])
    assert first == second
    med = statistics.median(len(p.prompt) for p in plan)
    assert abs(med - t["prompt_tokens"]["median"]) <= 256


# ---------------------------------------------------------------------------
# end-to-end arithmetic
# ---------------------------------------------------------------------------

def test_tail_is_over_all_samples():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 90) == 90
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile([5.0], 99) == 5.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_rate_is_over_the_whole_window():
    emits = {1: [0.5, 1.0, 1.5], 2: [9.0, 10.5]}
    assert stats.tokens_in(emits, 0.0, 10.0) == 4
    assert stats.rate(4, 0.0, 10.0) == 0.4


def test_stall_inside_the_window_moves_the_tail():
    steady = {r: [0.1 * i for i in range(1, 100)] for r in range(4)}
    base = stats.percentile(stats.itl_samples(steady, 0.0, 10.0), 95)
    stalled = {r: [t + (2.0 if t > 5.0 else 0.0) for t in ts]
               for r, ts in steady.items()}
    s = stats.itl_samples(stalled, 0.0, 20.0)
    assert max(s) > 2.0 and base == pytest.approx(0.1)
    # a stall outside the window is not counted
    assert max(stats.itl_samples(stalled, 0.0, 5.0)) == pytest.approx(0.1)


def test_ttft_counts_from_due():
    assert stats.ttft_samples({1: 10.0, 2: 11.0}, {1: 10.25}) == [0.25]


# ---------------------------------------------------------------------------
# work functions
# ---------------------------------------------------------------------------

DIMS = dict(num_hidden_layers=2, hidden_size=8, num_attention_heads=4,
            num_key_value_heads=2, head_dim=2, intermediate_size=16,
            vocab_size=32)
PEAKS = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def test_gemm_work_by_hand():
    assert work.gemm_flops((2, 3, 4)) == 48
    assert work.gemm_bytes((2, 3, 4)) == 2 * (6 + 12 + 8)
    assert work.min_seconds(48, 52, PEAKS) == (5.2, "memory")
    assert work.min_seconds(5000, 10, PEAKS) == (50.0, "compute")
    shapes = work.layer_gemms(DIMS, 3)
    assert shapes == [(3, 8, 8), (3, 8, 4), (3, 8, 4), (3, 8, 8),
                      (3, 8, 16), (3, 8, 16), (3, 16, 8)]
    assert work.step_gemms(DIMS, 3, 1)[-1] == (1, 8, 32)
    assert len(work.step_gemms(DIMS, 3, 1)) == 15


def test_attention_work_by_hand():
    flops, nbytes = work.decode_attention(DIMS, [3, 5])
    assert flops == 4 * 8 * 4 * 2           # 4 * sum(ctx) * hq * hd
    assert nbytes == 2 * (2 * 8 * 2 * 2 + 2 * 2 * 4 * 2)
    assert work.causal_attention_flops(DIMS, 3) == 4 * 4 * 2 * 6
    pf = work.prefill_flops(DIMS, 3)
    mm = sum(work.gemm_flops(s) for s in work.step_gemms(DIMS, 3, 1))
    assert pf == mm + 2 * 4 * 4 * 2 * 6


def test_peaks_refuse_an_unknown_device():
    assert work.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        work.load_peaks("cpu")


# ---------------------------------------------------------------------------
# trace reduction, on a trace recorded on a TPU v5e
# ---------------------------------------------------------------------------

def test_union_of_intervals():
    assert trace_lib.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3),
                                                                 (5, 8)]


def test_op_label():
    name = ("%copy.67 = bf16[28,1792,16,8,128]{4,3,2,1,0:T(8,128)(2,1)} "
            "copy(bf16[28,1792,16,8,128]{4,3,2,1,0} %get-tuple-element)")
    assert trace_lib.op_label(name) == "copy bf16[28,1792,16,8,128]"


def test_reduction_of_a_chip_trace(tmp_path):
    path = tmp_path / "t.xplane.pb"
    with gzip.open(TRACE) as src:
        path.write_bytes(src.read())
    s = trace_lib.reduce(str(path))
    assert s.devices == 1
    assert 0 < s.busy_s <= s.window_s
    assert {"gemm", "flash_decode", "flash_attention"} <= set(s.family_s)
    assert sum(s.family_s.values()) <= s.busy_s
    assert 0 < len(s.device_ops) <= 10 and 0 < len(s.idle_gaps) <= 10
    assert all(k.startswith("host:") for k, _ in s.idle_gaps)
    idle = sum(v for _, v in s.idle_gaps)
    assert idle == pytest.approx(s.window_s - s.busy_s, rel=1e-6)


# ---------------------------------------------------------------------------
# the float32 reference against the program's XLA forward
# ---------------------------------------------------------------------------

def _tiny(arch: str, dtype: str):
    """A reduced program configuration and the matching config file."""
    from repro.configs import get_config, reduced_config
    cfg = reduced_config(get_config(arch), dtype=dtype,
                         kv_cache_dtype=dtype, logits_dtype=dtype)
    base = run.load_cell({"qwen3-0.6b": "qwen3-0.6b.chat",
                          "qwen2.5-3b": "qwen2.5-3b.prefill-backlog"}[arch])
    conf = dict(base.config)
    conf.update(num_hidden_layers=cfg.num_layers, hidden_size=cfg.d_model,
                num_attention_heads=cfg.num_heads,
                num_key_value_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
                intermediate_size=cfg.d_ff, vocab_size=cfg.vocab_size)
    return cfg, conf, base


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "qwen2.5-3b"])
def test_reference_matches_the_program(arch):
    import jax.numpy as jnp
    from repro.core import use
    from repro.runtime.steps import forward
    from bench import weights
    from bench.reference import qwen

    cfg, conf, _ = _tiny(arch, "float32")
    dims = run.model_dims(conf)
    arch_flags = conf["architecture"]
    w = weights.make(dims, arch_flags, 3, dtype=jnp.float32)
    tokens = np.random.default_rng(0).integers(0, dims["vocab_size"],
                                               qwen.Q_BLOCK)
    with use(backend="xla"):
        logits, _, _ = forward(cfg, weights.to_program(w, arch_flags),
                               {"tokens": jnp.asarray(tokens)[None]})
    ref = qwen.logits_at(w, jnp.asarray(tokens, jnp.int32),
                         jnp.arange(qwen.Q_BLOCK), dims=check.dims_key(dims))
    got = np.asarray(logits[0])
    want = np.asarray(ref)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    # the part each architecture adds is in play
    key = "k_norm" if arch_flags["qk_norm"] else "bk"
    w2 = dict(w, **{key: w[key] * 0 + (1 if key == "k_norm" else 0)})
    moved = np.asarray(qwen.logits_at(w2, jnp.asarray(tokens, jnp.int32),
                                      jnp.arange(qwen.Q_BLOCK),
                                      dims=check.dims_key(dims)))
    assert np.abs(moved - want).max() > 1e-2


# ---------------------------------------------------------------------------
# whole runs at a tiny size
# ---------------------------------------------------------------------------

TINY_TRAFFIC = {
    "arrival": "poisson", "rate_per_s": 4, "lead_in_s": 1,
    "prompt_tokens": {"median": 32, "sigma": 0.5, "min": 16, "max": 64,
                      "round_up_to": 16},
    "answer_tokens": {"median": 12, "sigma": 0.5, "min": 6, "max": 24},
    "check_served_tokens": 64}
# The check's limit at this size, set as the cells' limits are: between
# the bf16 program's widest gap here (0.0028) and the fp8 control's
# (0.042), near their geometric mean.
TINY_LIMIT = 0.012


def _tiny_run(seed, *, tamper=None, control=False):
    from bench import control as control_lib
    cfg, conf, base = _tiny("qwen3-0.6b", "bfloat16")
    conf["serving"] = {"slots": 4, "page_size": 16, "pages": 64,
                       "max_context": 96}
    conf["check"] = {"served_logit_gap": TINY_LIMIT}
    cell = run.Cell("tiny.chat", 1, conf, TINY_TRAFFIC, base.end_to_end,
                    base.per_layer)
    out = {}

    def inspect(ref, w, dims, finished, rids, n_at):
        if control:
            out["control"] = control_lib.control_gap(ref, w, dims, finished,
                                                     rids, n_at)

    res = run.run_cell(cell, cfg, seed, 2.0, False, tamper=tamper,
                       inspect=inspect, say=lambda *_: None)
    return res, out.get("control")


@pytest.fixture(scope="module")
def sound_run():
    return _tiny_run(2 ** 31 + 99, control=True)


def test_sound_run_is_correct_and_reports_every_metric(sound_run):
    res, _ = sound_run
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == 8
    assert set(res["metrics"]) == {"ttft_p90_ms", "itl_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"


def test_control_reads_far_above_the_program(sound_run):
    """At this size too, the fp8 control's widest gap is more than three
    times the bf16 program's: the check tells the two apart."""
    res, control = sound_run
    program = res["checks"]["served_logit_gap"]["value"]
    print(f"program {program} control {control}")
    assert control > 3 * program
    assert check.judge({"gap": (program, TINY_LIMIT)})
    assert not check.judge({"gap": (control, TINY_LIMIT)})


def test_token_altered_where_produced_fails_the_check():
    def tamper(engine):
        step = engine._step

        def altered(*args):
            toks, cache, lengths = step(*args)
            return (toks + 1) % engine.cfg.vocab_size, cache, lengths

        engine._step = altered

    res, _ = _tiny_run(2 ** 31 + 99, tamper=tamper)
    assert res["correct"] is False
    gap = res["checks"]["served_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_off_a_tpu_no_result_and_a_nonzero_exit(capsys):
    assert run.main(["--workload", "qwen3-0.6b.chat", "--seed", "1",
                     "--seconds", "1"]) == run.NO_CHIP
    assert capsys.readouterr().out == ""


# ---------------------------------------------------------------------------
# BENCHMARK.json
# ---------------------------------------------------------------------------

def test_every_name_finds_its_files():
    b = _bench()
    for conf in b["configs"]:
        assert os.path.exists(os.path.join(ROOT, conf["file"]))
    for cell in b["workloads"]:
        run.load_cell(cell["name"])
    for m in b["per_layer"]:
        assert os.path.exists(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py"))
    fake = dict(setup_s=1.0, t_open=0.0, t_close=10.0, ttft=[0.1],
                itl=[0.02], tokens=5)
    for m in b["end_to_end"]:
        assert run.end_to_end(m["name"], fake) > 0


def test_configs_match_the_program():
    b = _bench()
    for conf in b["configs"]:
        with open(os.path.join(ROOT, conf["file"])) as f:
            c = json.load(f)
        assert c["source"] == conf["source"] and c["reduced"] == []
        run.program_config(c)
