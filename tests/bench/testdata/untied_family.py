"""A model family for the harness's tests only: Qwen's decoder handed to
the program untied, with an ``lm_head`` leaf that Qwen's tree lacks.

Every name the harness reads records its call in ``CALLS`` and then does
what ``bench/models/qwen.py`` does, so a test can see that a run went
through this module.  The head holds the embedding's transpose, so
Qwen's tied reference still computes what the program serves.
"""
import dataclasses

from bench.models import qwen

CALLS = []


def _recorded(name, fn):
    def call(*args, **kwargs):
        CALLS.append(name)
        return fn(*args, **kwargs)
    return call


dims = _recorded("dims", qwen.dims)
differences = _recorded("differences", qwen.differences)
make = _recorded("make", qwen.make)
step_gemms = _recorded("step_gemms", qwen.step_gemms)
attention_layers = _recorded("attention_layers", qwen.attention_layers)
decode_attention = _recorded("decode_attention", qwen.decode_attention)
prefill_flops = _recorded("prefill_flops", qwen.prefill_flops)
decode_flops = _recorded("decode_flops", qwen.decode_flops)


def to_program(w, d):
    CALLS.append("to_program")
    return dict(qwen.to_program(w, d), lm_head={"w": w["embed"].T})


def program_config(config):
    CALLS.append("program_config")
    return dataclasses.replace(qwen.program_config(config),
                               tie_embeddings=False)
