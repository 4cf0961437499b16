"""Cells of the benchmark cut to a size the CPU runs in seconds.

The widths shrink and nothing else: the block pattern, the traffic mix and
the harness are the cells' own.  Used by the tests and by ``calibrate.py``
when it rehearses on the CPU.
"""
from __future__ import annotations

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
BENCH_DIR = HERE.parent
ROOT = BENCH_DIR.parent
for p in (str(ROOT / "src"), str(BENCH_DIR)):
    if p not in sys.path:
        sys.path.insert(0, p)

import spec  # noqa: E402

SMOKE_WIDTHS = {
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 512,
    "mamba_d_state": 16, "mamba_headdim": 16, "chunk_size": 32,
}

PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
         "hbm_bytes": 1e9}


#: the cells of BENCHMARK.json
CELLS = {w["name"]: (w["config"], w["traffic"]) for w in
         spec.load_json(ROOT / "BENCHMARK.json")["workloads"]}


def smoke_cell(name: str, dtype: str = "float32", max_batch: int = 4,
               limit: float = 0.05) -> spec.Cell:
    cell = spec.load_cell(name, *CELLS[name])
    conf = dict(cell.config)
    conf.update({k: v for k, v in SMOKE_WIDTHS.items() if k in conf})
    conf["torch_dtype"] = dtype
    # two layers of a one-layer pattern, one period of a longer one
    conf["num_hidden_layers"] = len(conf["block_pattern"]) * (
        2 if len(conf["block_pattern"]) == 1 else 1)
    sizes = dict(cell.sizes, max_batch=max_batch,
                 check={"requests": 4, "batch": 2},
                 limits={"max_logit_gap": limit})
    return spec.Cell(name=name, chips=1, config=conf, traffic=cell.traffic,
                     sizes=sizes)


def hybrid_config(conf: dict) -> dict:
    """A smoke-width hybrid of the ``stack`` family: mamba2 layers beside a
    shared attention block, as the program's hybrids have them."""
    return dict(conf, name="hybrid-smoke", num_hidden_layers=3,
                tie_word_embeddings=True, hidden_act="gelu",
                mamba_d_state=16, mamba_d_conv=4, mamba_expand=2,
                mamba_headdim=16, chunk_size=32, time_step_min=0.001,
                time_step_max=0.1, time_step_floor=1e-4,
                block_pattern=[{"kind": "mamba2", "mlp": "none"}] * 2
                + [{"kind": "attn", "mlp": "glu", "shared": True}])


def kept_measured(monkeypatch) -> list:
    """Every ``harness.Measured`` that runs make from now on, in a list."""
    import dataclasses
    import harness
    made = []

    @dataclasses.dataclass
    class Kept(harness.Measured):
        def __post_init__(self):
            made.append(self)
    monkeypatch.setattr(harness, "Measured", Kept)
    return made
