"""Shared helpers of the benchmark's CPU tests: the repo root, and a way
to add a configuration, a traffic mix, a cell and metrics to a copy of the
benchmark the way a later change would add them: files and entries only."""

import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

TINY_WIDTHS = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
               "head_dim": 16, "intermediate_size": 128, "num_hidden_layers": 2,
               "vocab_size": 256}
TINY_OVERRIDES = {"tie_embeddings": True, "num_layers": 2, "d_model": 64, "num_heads": 4,
                  "num_kv_heads": 2, "head_dim": 16, "d_ff": 128, "vocab_size": 256,
                  "attn_chunk": 16}


def add_cell(root: Path, name: str, config: str, widths: dict, overrides: dict,
             mix: dict, cell: dict, metrics: dict[str, str] | None = None) -> None:
    """Add a configuration, a traffic mix, a cell and end-to-end metrics to
    the benchmark copy at ``root``: new files, and new entries in
    ``BENCHMARK.json``; no existing file changes otherwise."""
    c = json.loads((root / "bench/configs/qwen3_4b.json").read_text())
    c.update(widths)
    c["program"]["overrides"] = overrides
    (root / f"bench/configs/{config}.json").write_text(json.dumps(c))
    traffic = name.split(".", 1)[1]
    (root / f"bench/traffic/{traffic}.json").write_text(json.dumps(mix))
    (root / f"bench/cells/{name}.json").write_text(json.dumps(cell))
    m = json.loads((root / "BENCHMARK.json").read_text())
    if config not in {x["name"] for x in m["configs"]}:
        m["configs"].append({"name": config, "source": "test", "reduced": [],
                             "file": f"bench/configs/{config}.json", "why": "test"})
    m["workloads"].append({"name": name, "config": config, "traffic": traffic,
                           "chips": 1, "why": "test"})
    for e in m["end_to_end"]:
        if "workloads" in e:
            e["workloads"].append(name)
    for metric, source in (metrics or {}).items():
        (root / f"bench/metrics/{metric}.py").write_text(source)
        m["end_to_end"].append({"name": metric, "unit": "1", "better": "higher",
                                "bound": 0.05, "source": "host_clock",
                                "workloads": [name]})
    (root / "BENCHMARK.json").write_text(json.dumps(m))
