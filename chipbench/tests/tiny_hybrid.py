"""A hybrid cell small enough for the CPU: a tiny Jamba-style model (four
layers, attention at layers 1 and 3) under a short chat mix, added to a
checkout of ``tiny_cells.make_checkout`` as new files only."""
import json
from pathlib import Path

from tiny_cells import TINY_CHAT, make_checkout

TINY_JAMBA = {
    "name": "tiny-jamba", "source": "test", "driver": "serve_hybrid",
    "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 1,
    "num_hidden_layers": 4, "attn_layer_period": 2,
    "attn_layer_offset": 1, "num_experts": 1,
    "mamba_d_state": 4, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_dt_rank": 8, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "vocab_size": 509,
    "rms_norm_eps": 1e-06, "sliding_window": None,
    "tie_word_embeddings": True,
    "reduced": [],
    "deployment": {"mem_arch": "16B-offset", "page_len": 8, "lanes": 4,
                   "max_seq": 64, "dtype": "bfloat16",
                   "attn_impl": "dense", "alloc_policy": "seq-skew"},
    "correct": {"max_logit_gap": 0.15},
}
CELL = "tiny-jamba.tiny-hybrid-chat"


def make_hybrid_checkout(root: Path) -> Path:
    """``make_checkout`` plus the tiny hybrid cell, which reports the
    serving metrics and the hybrid's per-layer metrics."""
    make_checkout(root)
    cfile = f"chipbench/configs/{TINY_JAMBA['name']}.json"
    (root / cfile).write_text(json.dumps(TINY_JAMBA))
    (root / "chipbench" / "traffic" / "tiny-hybrid-chat.json").write_text(
        json.dumps(dict(TINY_CHAT, driver="serve_hybrid")))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": TINY_JAMBA["name"], "source": "test",
                             "file": cfile, "reduced": [],
                             "why": "CPU test"})
    bench["workloads"].append({"name": CELL, "config": TINY_JAMBA["name"],
                               "traffic": "tiny-hybrid-chat", "chips": 1,
                               "why": "CPU test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "jamba2-3b.hybrid-chat" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
