"""Smoke-width copies of the benchmark's cells, for the CPU tests."""

import copy
import dataclasses


#: published keys replaced to run a configuration at smoke width on a CPU
SMALL = {
    "qwen3-1.7b": dict(hidden_size=64, intermediate_size=128,
                       num_hidden_layers=2, num_attention_heads=4,
                       num_key_value_heads=2, head_dim=32, vocab_size=512),
    "starcoder2-3b": dict(hidden_size=64, intermediate_size=128,
                          num_hidden_layers=2, num_attention_heads=4,
                          num_key_value_heads=2, vocab_size=512),
}

#: the widths at which the qwen3 stack's control and altered-token tests
#: run: at the widths above the int4 cache's rounding flips swamp the
#: weights' precision, and the control reads no higher than the program
WIDE = {
    "qwen3-1.7b": dict(hidden_size=256, intermediate_size=512,
                       num_hidden_layers=4, num_attention_heads=4,
                       num_key_value_heads=2, head_dim=64, vocab_size=32768),
}

#: the correctness limit of the smoke cells, per configuration.  Over eight
#: seeds the program read widest gaps of at most 0.263 (qwen3-1.7b stack
#: at ``WIDE``, chat-shared and doc-decode; at most 0.079 at ``SMALL``) and
#: 0.029 (starcoder2-3b stack); the control, w4a8 weights in the program's
#: place, at least 0.578 (at ``WIDE``) and 0.237 over three.
SMOKE_GAP_LIMIT = {"qwen3-1.7b": 0.4, "starcoder2-3b": 0.08}


def shrink_config(config: dict) -> dict:
    c = copy.deepcopy(config)
    c.update(SMALL[c["program_model"]])
    c["stack"].update(slots=2, max_len=256, chunk=32, max_new_cap=64)
    c["limits"].update(max_logit_gap=SMOKE_GAP_LIMIT[c["program_model"]],
                       mean_logit_gap=None, min_tokens=8, max_requests=4)
    return c


def shrink_cell(cell, wide: bool = False):
    c = dataclasses.replace(cell, config=shrink_config(cell.config),
                            mix=copy.deepcopy(cell.mix))
    if wide:
        c.config.update(WIDE.get(c.config["program_model"], {}))
    m = c.mix
    m["warmup_s"] = 1
    if m["kind"] == "closed":
        m.update(users=c.config["stack"]["slots"], stagger_s=0.2)
    if "checked_requests" in m:
        m["checked_requests"] = c.config["stack"]["slots"]
    else:
        m["rate_per_s"] = 3.0
    if "prefix" in m:
        m["prefix"]["tokens"] = 64
    m["prompt"].update(median=40, min=32, max=96)
    m["output"].update(median=6, min=2, max=12)
    return c
