"""Tiny cells for CPU tests: the same harness and program paths at a
size a test run holds (``ModelConfig.reduced`` widths, in bfloat16 with
full remat and scanned layers, as the chip cells run)."""

from __future__ import annotations

import json
from pathlib import Path

from chipbench.common import Cell

BENCH = Path(__file__).resolve().parents[1]

TINY_CONFIG = {
    "name": "tiny", "registry": "smollm-135m",
    "num_hidden_layers": 2, "hidden_size": 128, "intermediate_size": 256,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "vocab_size": 512, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
    "attention_bias": False, "tie_word_embeddings": True,
    "program": {"dtype": "bfloat16", "remat": "full"},
}


def tiny_program_config(config):
    """The program's config for ``TINY_CONFIG`` (or its biased twin)."""
    from repro.configs import get_config

    return get_config("smollm-135m").reduced(
        dtype=config["program"]["dtype"], remat="full", scan_layers=True,
        qkv_bias=config["attention_bias"], rope_theta=config["rope_theta"])


def train_cell(traffic="train_late", **overrides) -> Cell:
    tr = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())
    tr.update(seq_len=32, window_batches=4, trace_seconds=1.0,
              stream=dict(tr["stream"], vocab=256))
    tr.update(overrides)
    limits = {"first_loss_gap": 1.2e-4, "grad_gap": 6e-3, "change1_gap": 1.5e-3,
              "change_gap": 1.5e-3}
    return Cell("tiny.train", 1, dict(TINY_CONFIG), tr, limits, {})


def serve_cell(**overrides) -> Cell:
    tr = json.loads((BENCH / "traffic" / "chat.json").read_text())
    tr.update(slots=4, max_len=128, arena_blocks=24, prefill_chunk=32, prefill_bucket=16, drain_s=30,
              trace_seconds=1.0, arrivals={"process": "poisson", "rate_per_s": 4.0},
              prompt_len={"dist": "lognormal", "median": 24, "sigma": 0.8, "min": 4, "max": 80},
              output_len={"dist": "lognormal", "median": 8, "sigma": 0.8, "min": 2, "max": 40},
              check={"min_tokens": 1000, "min_requests": 3})
    tr.update(overrides)
    config = dict(TINY_CONFIG, attention_bias=True, rope_theta=1e6)
    return Cell("tiny.serve", 1, config, tr, {"served_gap": 0.02}, {})
