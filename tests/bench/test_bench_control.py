"""The correctness check's control, kept at a size a CPU test can hold.

The control puts the plain reference in the program's place one precision
below the configuration's bfloat16 (float8 operands in every projection).
On a small decoder (d_model 128, 2 layers, a vocabulary of 4096) served
through the real scheduler, the program's widest gap stays under the
small cell's limit on every seed, and the control's exceeds it on every
seed: the comparison that decides ``correct`` tells the two apart.  The
readings at the cells' own sizes come from ``bench/control.py`` on the
chip (see PERF.md).

Readings on the CPU, seeds 1-8, 384 checked tokens each: program
0.009-0.030, control 0.40-0.68; the limit here is 0.1.
"""

import pytest

from _bench_fixtures import add_cell

SMALL = {"hidden_size": 128, "num_attention_heads": 4, "num_key_value_heads": 2,
         "head_dim": 32, "intermediate_size": 256, "num_hidden_layers": 2,
         "vocab_size": 4096}
SMALL_OVERRIDES = {"tie_embeddings": True, "num_layers": 2, "d_model": 128,
                   "num_heads": 4, "num_kv_heads": 2, "head_dim": 32, "d_ff": 256,
                   "vocab_size": 4096, "attn_chunk": 16}
LIMIT = 0.1


@pytest.fixture
def small_root(bench_copy):
    add_cell(bench_copy, "small.ctl", "small", SMALL, SMALL_OVERRIDES,
             {"arrival": {"kind": "all_at_start"},
              "prompt_len": {"kind": "uniform", "min": 24, "max": 64},
              "output_len": {"kind": "fixed", "value": 16},
              "block_size": 16},
             {"slots": 4, "check_tokens": 256, "limits": {"max_logit_gap": LIMIT}})
    return bench_copy


def test_control_fails_where_the_program_passes(small_root):
    from bench import control

    rows = control.readings(small_root, "small.ctl", [1, 2, 3], require_tpu=False,
                            requests_per_slot=4)
    for r in rows:
        assert r["tokens"] >= 256
        assert r["program"] <= LIMIT < r["control"], r


def test_control_verdict_needs_every_seed_separated():
    from bench import control

    rows = [{"seed": 1, "program": 0.02, "control": 0.5},
            {"seed": 2, "program": 0.03, "control": 0.08}]
    v = control.verdict(rows, LIMIT)
    assert (v["lower"], v["upper"]) == (0.03, 0.08)
    assert v["not_separated"] == [2] and not v["separated"]
    assert control.verdict(rows[:1], LIMIT)["separated"]
