"""CPU tests of granite-4.0-h-small in the benchmark: the served model
against the plain float32 reference (``bench/reference/granite.py``)
through ``ContinuousBatchingEngine``, a whole tiny run of the cell whose
output check passes while its fp8 control does not, the family module's
layout check and work counts against hand formulas, the cut
configuration's check, and the two roofline readers it brings.

Run from the repository root:  python -m pytest -q tests/bench
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import check, run, work  # noqa: E402

CELL = "granite-4.0-h-small.chat-high-rate"
GRANITE = run.family("granite")
PEAKS = work.load_peaks("TPU v5 lite")
# Small widths: one period of ten layers, 8 router experts of which 4
# are held (from expert 2), top-3, chunk 8; the published scalars.
SMALL = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
             intermediate_size=32, shared_intermediate_size=48,
             num_local_experts=4, num_experts_per_tok=3, mamba_n_heads=8,
             mamba_d_head=16, mamba_d_state=16, mamba_chunk_size=8,
             vocab_size=512, first_held_expert=2)


def _config():
    with open(os.path.join(ROOT, "bench", "configs",
                           "granite-4.0-h-small.json")) as f:
        return json.load(f)


def _small(dtype):
    """The small configuration file and the program configuration that
    serves it in ``dtype``."""
    from repro.configs import get_config
    conf = dict(_config(), **SMALL)
    conf["published"] = dict(conf["published"], num_local_experts=8)
    cfg = dataclasses.replace(
        get_config("granite-4.0-h-small"), num_layers=10, d_model=64,
        num_heads=4, num_kv_heads=2, head_dim=16, d_ff=32,
        shared_expert_d_ff=48, num_experts=8, num_experts_per_tok=3,
        experts_held=4, first_expert_held=2, ssm_state=16, ssm_head_dim=16,
        ssm_chunk=8, vocab_size=512, dtype=dtype, kv_cache_dtype=dtype,
        logits_dtype=dtype)
    assert GRANITE.differences(cfg, conf) == []
    return conf, cfg


# ---------------------------------------------------------------------------
# the served model against the reference
# ---------------------------------------------------------------------------

PROMPTS = (21, 30, 13)
MAX_NEW = 9


def _served_logits(dtype):
    """Serve three requests on two slots of a pool small enough to evict,
    and record the logits of every prefill (the engine's own prefill
    program) and of every decode step (the decode step's forward over the
    engine's cache, just before the step runs), by request and position
    predicted from.  Returns (weights, dims, sequences, logits, engine)."""
    import jax
    import jax.numpy as jnp
    from repro.models.attention import PageSpec
    from repro.runtime.batching import ContinuousBatchingEngine, Request
    from repro.runtime.steps import forward

    conf, cfg = _small(dtype)
    dims = GRANITE.dims(conf)
    w = GRANITE.make(dims, 7, dtype=jnp.dtype(dtype))
    eng = ContinuousBatchingEngine(cfg, GRANITE.to_program(w, dims),
                                   num_slots=2, spec=PageSpec(8, 8, 8))
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, 512, n).astype(np.int32),
                    max_new=MAX_NEW) for i, n in enumerate(PROMPTS)]
    got = {}
    for r in reqs:
        logits = eng._prefill_fn(len(r.prompt))(
            eng.params, {"tokens": jnp.asarray(r.prompt)[None]})[0]
        got[(r.rid, len(r.prompt) - 1)] = np.asarray(logits[0])

    step = eng._step
    decode_logits = jax.jit(lambda params, cache, tokens, positions: forward(
        cfg, params, {"tokens": tokens}, cache=cache,
        positions=positions)[0][:, -1])

    def spy(params, cache, tokens, lengths, active):
        positions = jnp.where(active, lengths, -1)[:, None]
        lg = np.asarray(decode_logits(params, cache, tokens, positions))
        for i, s in enumerate(eng.slots):
            if s is not None and bool(active[i]):
                got[(s.req.rid, int(lengths[i]))] = lg[i]
        return step(params, cache, tokens, lengths, active)

    eng._step = spy
    for r in reqs:
        eng.submit(r)
    while eng.queue or any(s is not None for s in eng.slots):
        eng.step()
    seqs = {r.rid: np.concatenate([r.prompt, eng.finished[r.rid].generated])
            for r in reqs}
    return w, dims, seqs, got, eng


def _worst_relative_gap(dtype):
    """Widest |program - reference| logit over every prefill and decode
    position served, over the reference's largest |logit|."""
    import jax.numpy as jnp
    from bench.reference import granite as ref

    w, dims, seqs, got, eng = _served_logits(dtype)
    w32 = {k: v.astype(jnp.float32) for k, v in w.items()}
    worst = 0.0
    for rid, seq in seqs.items():
        toks = np.zeros(ref.Q_BLOCK, np.int32)
        toks[:len(seq)] = seq
        want = np.asarray(ref.logits_at(
            w32, jnp.asarray(toks), jnp.arange(len(seq) - 1),
            dims=check.dims_key(dims)))
        at = sorted(p for r, p in got if r == rid)
        # every position from the prompt's last on was served
        assert at == list(range(PROMPTS[rid] - 1, len(seq) - 1))
        for p in at:
            worst = max(worst, float(np.abs(got[(rid, p)] - want[p]).max()
                                     / np.abs(want[p]).max()))
    return worst, eng


# The float32 program and the float32 reference compute the same sums in
# other orders: the chunked SSD scan against the sequential one, experts
# grouped against dense, attention in blocks.  Over ten layers that
# reads 2.8e-6 of the largest logit, so 1e-4 leaves room for other
# orders.  The bf16 program reads 0.33: it rounds each activation to
# 2^-9 relative.
REL_TOL = 1e-4


def test_served_prefill_and_decode_match_the_reference():
    worst, eng = _worst_relative_gap("float32")
    assert eng.evictions > 0, "the case must evict and re-admit"
    assert worst < REL_TOL, worst


def test_a_lower_precision_fails_the_comparison():
    worst, _ = _worst_relative_gap("bfloat16")
    assert worst > REL_TOL, worst


# ---------------------------------------------------------------------------
# a whole tiny run of the cell
# ---------------------------------------------------------------------------

TINY_TRAFFIC = {
    "arrival": "poisson", "rate_per_s": 4, "lead_in_s": 1,
    "prompt_tokens": {"median": 32, "sigma": 0.5, "min": 16, "max": 64,
                      "round_up_to": 16},
    "answer_tokens": {"median": 12, "sigma": 0.5, "min": 6, "max": 24},
    "check_served_tokens": 64}
# At this size (8 experts, top-3) a rounding can flip a near-tied
# router pick, which moves a token's logits by a step: per request the
# bf16 program's widest gap read up to 4.4e-4 and the fp8 control's
# 3.1e-4 to 1.1e-3 (one seed, every request), so no limit tells the two
# apart here, and this run checks that the harness serves and checks the
# family end to end.  The limit has 4.5 times room above the program's
# widest gap; the served logits' own comparison above is the tight one.
TINY_LIMIT = 2e-3


@pytest.fixture(scope="module")
def tiny_run():
    from bench import control
    conf, cfg = _small("bfloat16")
    conf["serving"] = {"slots": 4, "page_size": 16, "pages": 64,
                       "max_context": 96}
    conf["check"] = {"served_logit_gap": TINY_LIMIT}
    base = run.load_cell(CELL)
    cell = run.Cell("tiny.granite", 1, conf, TINY_TRAFFIC, base.end_to_end,
                    base.per_layer)
    out = {}

    def inspect(ref, w, dims, finished, rids, n_at):
        out["control"] = control.control_gap(ref, w, dims, finished, rids,
                                             n_at)

    res = run.run_cell(cell, cfg, 2 ** 31 + 99, 2.0, False, inspect=inspect,
                       say=lambda *_: None)
    return res, out["control"]


def test_tiny_run_is_correct_and_reports_its_metrics(tiny_run):
    res, control = tiny_run
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["checks"]["served_logit_gap"]["value"] <= TINY_LIMIT
    assert control > 0  # the fp8 control reads the same tokens


# ---------------------------------------------------------------------------
# the family module and the cut configuration
# ---------------------------------------------------------------------------

def test_layout_fits_the_program():
    import jax
    from bench import weights
    from repro.launch.serve import load_params
    conf, cfg = _small("bfloat16")
    d = GRANITE.dims(conf)
    weights.check_layout(GRANITE.to_program(GRANITE.make(d, 1), d),
                         jax.eval_shape(lambda: load_params(cfg)))
    # at the published widths of the cut, in shapes only
    conf = _config()
    d = GRANITE.dims(conf)
    cfg = GRANITE.program_config(conf)
    made = jax.eval_shape(lambda: GRANITE.to_program(GRANITE.make(d, 1), d))
    weights.check_layout(made, jax.eval_shape(lambda: load_params(cfg)))
    n = sum(x.size for x in jax.tree.leaves(made))
    norms = (2 * cfg.num_layers + 1) * cfg.d_model  # param_count has none
    assert n == cfg.param_count() + norms
    assert 2.41e9 < n < 2.42e9


def test_the_cut_configuration():
    conf = _config()
    run.check_cuts(conf)
    cfg = GRANITE.program_config(conf)
    assert GRANITE.differences(cfg, conf) == []
    assert (cfg.num_layers, cfg.num_experts, cfg.held_experts) == (10, 72, 9)
    d = GRANITE.dims(conf)
    assert d["period"] == 10 and d["layer_types"][5] == "attention"
    for k, v in conf["published"].items():
        assert conf[k] != v
    bad = dict(conf, published={"num_hidden_layers": 40})
    with pytest.raises(SystemExit, match="num_local_experts"):
        run.check_cuts(bad)
    # a program that holds other experts does not serve it
    assert GRANITE.differences(dataclasses.replace(cfg, first_expert_held=9),
                               conf)


def test_work_counts_by_hand():
    d = GRANITE.dims(_config())
    D, F, Fs, V = 4096, 768, 1536, 100352
    proj, d_in = 2 * 8192 + 2 * 128 + 128, 8192
    m = 48
    shapes = GRANITE.step_gemms(d, m, m)
    mamba = [(m, D, proj), (m, d_in, D)]
    attn = [(m, D, 4096), (m, D, 1024), (m, D, 1024), (m, 4096, D)]
    shared = [(m, D, Fs), (m, D, Fs), (m, Fs, D)]
    assert shapes == ((mamba + shared) * 5 + attn + shared
                      + (mamba + shared) * 4 + [(m, D, V)])
    assert GRANITE.attention_layers(d) == 1
    assert GRANITE.expected_expert_rows(d, 72) == 72 * 10 * 9 / 72 * 10
    flops, nbytes = GRANITE.expert_work(d, 100, 7)
    assert flops == 100 * 3 * 2 * D * F
    assert nbytes == 2 * (7 * 3 * D * F + 100 * (3 * D + 3 * F))
    # SSD: 300 tokens = a chunk of 256 and one of 44, nine layers
    flops, nbytes = GRANITE.ssd_prefill_work(d, 300)
    pairs = 256 * 257 / 2 + 44 * 45 / 2
    want = 2 * pairs * (128 + 128 * 64) + 4 * 300 * 128 * 64 * 128
    assert flops == 9 * want
    assert nbytes == 9 * (300 * (2 * (2 * 8192 + 2 * 128) + 4 * 128)
                          + 4 * 128 * 64 * 128)
    att = GRANITE.decode_attention(d, [10, 20])
    assert att == (4 * 30 * 32 * 128, 2 * (2 * 30 * 8 * 128 + 4 * 32 * 128))
    # decode: the dense matmuls, attention, and per token the router,
    # the expected expert rows, the conv and one SSD step
    per_token = (2 * D * 72 * 10 + 1.25 * 10 * 3 * 2 * D * F
                 + 9 * 2 * 4 * (8192 + 256) + 9 * 5 * 128 * 64 * 128)
    mm = sum(work.gemm_flops(s) for s in GRANITE.step_gemms(d, 2, 2))
    assert GRANITE.decode_flops(d, [10, 20]) == pytest.approx(
        mm + att[0] + 2 * per_token)


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------

def _reading(model, family_s, phase_s, prefills):
    d = model.dims(_config() if model is GRANITE else json.load(open(
        os.path.join(ROOT, "bench", "configs", "qwen3-0.6b.json"))))
    return run.Reading(
        dims=d, model=model, peaks=PEAKS, window_s=1.0,
        work=types.SimpleNamespace(prefills=prefills, decode_contexts=[[5]],
                                   steps=1),
        phase_s=phase_s, trace=types.SimpleNamespace(family_s=family_s),
        memory_peak_bytes=None)


def test_rooflines_of_the_expert_gemms_and_the_ssd_scan():
    phase = {"expert_rows.decode": 600.0, "experts_hit.decode": 90.0,
             "expert_rows.prefill": 5000.0, "experts_hit.prefill": 90.0}
    r = _reading(GRANITE, {"grouped_gemm": 0.5, "ssd_chunk": 0.25}, phase,
                 [300, 64])
    d = r.dims
    least = sum(max(f / PEAKS["bf16_flops_per_s"],
                    b / PEAKS["hbm_bytes_per_s"])
                for f, b in (GRANITE.expert_work(d, 600, 90),
                             GRANITE.expert_work(d, 5000, 90)))
    assert run.read_metric("grouped_gemm_roofline", r) == pytest.approx(
        100 * least / 0.5)
    least = sum(max(f / PEAKS["bf16_flops_per_s"],
                    b / PEAKS["hbm_bytes_per_s"])
                for f, b in (GRANITE.ssd_prefill_work(d, 300),
                             GRANITE.ssd_prefill_work(d, 64)))
    assert run.read_metric("ssd_chunk_roofline", r) == pytest.approx(
        100 * least / 0.25)
    # nothing to read: no kernel time, no counters, a family with no such
    # work
    for fam, ph, model in (({}, phase, GRANITE),
                           ({"grouped_gemm": 0.5}, {}, GRANITE),
                           ({"grouped_gemm": 0.5, "ssd_chunk": 0.25}, phase,
                            run.family("qwen"))):
        r = _reading(model, fam, ph, [300])
        assert run.read_metric("grouped_gemm_roofline", r) is None
    assert run.read_metric("ssd_chunk_roofline", _reading(
        run.family("qwen"), {"ssd_chunk": 0.25}, phase, [300])) is None
