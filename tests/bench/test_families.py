"""CPU tests of the model family modules (``bench/models/``) and of what
the harness takes from a configuration for them: Qwen's weights, dims
and work counts against numbers recorded from the formulas the harness
held before the families, a missing family, cut configurations, list
valued dims, and the trace rows that match kernels by name.

Run from the repository root:  python -m pytest -q tests/bench
"""
from __future__ import annotations

import hashlib
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import check, run, work  # noqa: E402
from bench import trace as trace_lib  # noqa: E402

CONFIGS = ("qwen3-0.6b", "qwen2.5-3b")
with open(os.path.join(ROOT, "tests", "bench", "testdata",
                       "qwen_recorded.json")) as _f:
    RECORDED = json.load(_f)


def _config(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


def _digest(shapes):
    return hashlib.sha256(json.dumps([list(s) for s in shapes]).encode()
                          ).hexdigest()[:16]


# ---------------------------------------------------------------------------
# Qwen: nothing moved
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", CONFIGS)
def test_qwen_weights_are_bit_identical_to_those_recorded(name):
    conf = _config(name)
    assert conf["model"] == "qwen"
    model = run.family(conf["model"])
    w = model.make(model.dims(dict(conf, **RECORDED["TINY"])),
                   RECORDED["SEED"])
    got = {k: [list(v.shape), str(v.dtype), hashlib.sha256(
        np.asarray(v).view(np.uint16).tobytes()).hexdigest()[:16]]
        for k, v in sorted(w.items())}
    assert got == RECORDED["weights"][name]


@pytest.mark.parametrize("name", CONFIGS)
def test_qwen_work_counts_are_those_recorded(name):
    conf = _config(name)
    model = run.family(conf["model"])
    rec = RECORDED["work"][name]
    d = model.dims(conf)
    assert d == rec["dims"]
    assert model.attention_layers(d) == rec["attention_layers"]
    peaks = work.load_peaks("TPU v5 lite")
    for m, u in RECORDED["ROWS"]:
        shapes = model.step_gemms(d, m, u)
        assert [len(shapes), _digest(shapes),
                work.gemm_min_seconds(shapes, peaks)] == \
            rec["step_gemms"][f"{m},{u}"]
    assert [list(model.decode_attention(d, c))
            for c in RECORDED["DECODE"]] == rec["decode_attention"]
    assert [model.decode_flops(d, c)
            for c in RECORDED["DECODE"]] == rec["decode_flops"]
    assert [model.causal_attention_flops(d, n)
            for n in RECORDED["PREFILL"]] == rec["causal_attention_flops"]
    assert [model.prefill_flops(d, n)
            for n in RECORDED["PREFILL"]] == rec["prefill_flops"]


def test_the_family_is_loaded_once():
    assert run.family("qwen") is run.family("qwen")


def test_a_missing_family_names_the_path_it_looked_for():
    want = os.path.join(ROOT, "bench", "models", "no-such-family.py")
    with pytest.raises(SystemExit, match=want):
        run.family("no-such-family")
    want = os.path.join(ROOT, "tests", "bench", "testdata", "none.py")
    with pytest.raises(SystemExit, match=want):
        run.family("tests/bench/testdata/none.py")


# ---------------------------------------------------------------------------
# cut configurations
# ---------------------------------------------------------------------------

def _cut(**changes):
    """qwen3-0.6b cut to 2 of its 28 layers, as a configuration states it."""
    conf = dict(_config("qwen3-0.6b"), num_hidden_layers=2,
                reduced=["num_hidden_layers"],
                published={"num_hidden_layers": 28},
                deployment="14 pipeline stages of 2 layers, one a chip")
    conf.update(changes)
    return {k: v for k, v in conf.items() if v is not None}


def test_a_cut_configuration_is_served_and_compared_at_its_cut_sizes():
    import dataclasses
    conf = _cut()
    run.check_cuts(conf)
    model = run.family(conf["model"])
    served = run.program_config(conf)
    assert served.num_layers == 2
    run.check_program(served, conf, model)
    published = dataclasses.replace(served, num_layers=28)
    with pytest.raises(SystemExit, match="num_hidden_layers"):
        run.check_program(published, conf, model)


@pytest.mark.parametrize("changes, why", [
    ({"published": None}, "num_hidden_layers: cut without its published"),
    ({"published": {"num_hidden_layers": 28, "vocab_size": 151936}},
     "vocab_size: a published value, but not in reduced"),
    ({"deployment": None}, "no deployment stated"),
    ({"deployment": ""}, "no deployment stated"),
])
def test_a_cut_must_give_its_published_value_and_deployment(changes, why):
    with pytest.raises(SystemExit, match=why):
        run.check_cuts(_cut(**changes))


def test_dims_key_takes_list_values():
    dims = {"layer_types": ["mamba", "attention"], "hidden_size": 8,
            "groups": [[1, 2], [3]]}
    key = check.dims_key(dims)
    assert key == (("groups", ((1, 2), (3,))), ("hidden_size", 8),
                   ("layer_types", ("mamba", "attention")))
    assert hash(key) == hash(check.dims_key(dict(reversed(dims.items()))))


# ---------------------------------------------------------------------------
# trace rows matched by kernel name
# ---------------------------------------------------------------------------

TABLE = ("s32[96,8]{1,0} %p0, bf16[9,4096,768]{2,1,0} %p1, "
         "bf16[1,2048,4096]{2,1,0} %p2")


@pytest.mark.parametrize("row, family", [
    ("%grouped_gemm.3 = bf16[1,2048,768]{2,1,0} custom-call(" + TABLE
     + "), custom_call_target=\"tpu_custom_call\"", "grouped_gemm"),
    ("%grouped_gemm = bf16[1,2048,768]{2,1,0} custom-call(" + TABLE
     + "), custom_call_target=\"tpu_custom_call\"", "grouped_gemm"),
    ("%ssd_chunk.1 = f32[8,128,64,128]{3,2,1,0} custom-call("
     "bf16[8,256,128,64]{3,2,1,0} %x, f32[8,256,128]{2,1,0} %a), "
     "custom_call_target=\"tpu_custom_call\"", "ssd_chunk"),
    # the same operands under another kernel's name stay with the signature
    ("%gemm.7 = bf16[1,2048,768]{2,1,0} custom-call(" + TABLE
     + "), custom_call_target=\"tpu_custom_call\"", "gemm"),
])
def test_kernel_rows_land_in_their_family(row, family):
    assert trace_lib.classify(row, trace_lib.load_names()) == family
