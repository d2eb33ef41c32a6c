"""Cells small enough for the CPU, added to a copy of the benchmark as new
files only: a tiny MiniCPM-style model under a short chat mix, and a short
Mixtral-style decode step priced over every kind of memory."""
import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent

TINY_LM = {
    "name": "tiny-lm", "source": "test", "driver": "serve",
    "config": {"hidden_size": 64, "intermediate_size": 128,
               "num_attention_heads": 4, "num_key_value_heads": 4,
               "num_hidden_layers": 2, "vocab_size": 509,
               "rms_norm_eps": 1e-05, "rope_theta": 10000.0,
               "scale_emb": 12, "dim_model_base": 16, "scale_depth": 1.4,
               "tie_word_embeddings": True},
    "reduced": [],
    "deployment": {"mem_arch": "16B-offset", "page_len": 8, "lanes": 4,
                   "max_seq": 64, "dtype": "bfloat16",
                   "attn_impl": "dense", "alloc_policy": "seq-skew"},
    "correct": {"max_logit_gap": 0.15},
}

TINY_CHAT = {
    "driver": "serve", "loop": "closed", "shuffle_block": 4,
    "n_requests": 40,
    "prompt": {"dist": "lognormal", "median": 12, "sigma": 0.5, "min": 8,
               "max": 24, "round_to": 8},
    "output": {"dist": "lognormal", "median": 10, "sigma": 0.4, "min": 4,
               "max": 16},
    "max_total": 64,
    "check": {"sample_tokens": 400, "max_requests": 40, "min_tokens": 10},
}

TINY_MOE = {
    "name": "tiny-moe", "source": "test", "driver": "price",
    "config": {"hidden_size": 64, "intermediate_size": 128,
               "num_attention_heads": 4, "num_key_value_heads": 2,
               "num_hidden_layers": 3, "num_local_experts": 4,
               "num_experts_per_tok": 2, "vocab_size": 512},
    "reduced": [],
    "deployment": {"capacity_factor": 1.25},
    "correct": {"mismatched_fields": 0},
}

TINY_PRICE = {
    "driver": "price", "batch": 4, "position": 40, "page_len": 8,
    "page_map": "16B-offset", "block_ops": 64, "fresh_trace_per_pass": True,
    "memories": ["4R-1W", "4R-2W", "4R-1W-VB", "16B", "16B-offset",
                 "8B-xor-bcast", "4B-fold", "12B", "6B-offset", "4x4B-g64",
                 "2x8B-g32", "4x3B"],
    "check": {"sample_passes": 2},
}


def make_checkout(root: Path) -> Path:
    """A checkout holding the benchmark's files plus two tiny cells
    (``tiny-lm.chat`` and ``tiny-moe.price``) added as new files only."""
    shutil.copytree(BENCH, root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for conf, traffic, tname in ((TINY_LM, TINY_CHAT, "tiny-chat"),
                                 (TINY_MOE, TINY_PRICE, "tiny-price")):
        cfile = f"chipbench/configs/{conf['name']}.json"
        (root / cfile).write_text(json.dumps(conf))
        (root / "chipbench" / "traffic" / f"{tname}.json").write_text(
            json.dumps(traffic))
        bench["configs"].append({"name": conf["name"], "source": "test",
                                 "file": cfile, "reduced": [],
                                 "why": "CPU test"})
        bench["workloads"].append({"name": f"{conf['name']}.{tname}",
                                   "config": conf["name"], "traffic": tname,
                                   "chips": 1, "why": "CPU test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            tiny = ("tiny-lm.tiny-chat" if any(
                w.startswith("minicpm") for w in m["workloads"])
                else "tiny-moe.tiny-price")
            m["workloads"].append(tiny)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
