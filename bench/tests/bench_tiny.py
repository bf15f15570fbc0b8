"""A tiny copy of the benchmark tree for CPU tests: the same cells, files
and code, at widths a test run can hold (Pallas kernels in interpret mode)."""

import json
import pathlib
import shutil

ROOT = pathlib.Path(__file__).resolve().parents[2]

# head_dim as published; the smallest widths at which a sound run's numbers
# stay under the cells' limits (bf16 rounding shrinks with leaf size)
WIDTHS = {"hidden_size": 256, "num_attention_heads": 4, "num_key_value_heads": 2,
          "head_dim": 128, "intermediate_size": 768}
OVERRIDE = {"d_model": 256, "num_heads": 4, "num_kv_heads": 2, "head_dim": 128,
            "d_ff": 768, "vocab_pad_to": 64}

# the train cells' limits at these widths, set as the cells' own are, from
# readings on the CPU: above the largest of sound runs (loss_gap 4.1e-4,
# grad_gap 4.5e-4, change_gap 1.4e-4 over 8 seeds) and below the least of
# the fp8 control (grad_gap 5.4e-3, change_gap 5.3e-4 over 3 seeds); here
# the first step's loss does not tell the two apart (control 1.1e-4 and up)
TRAIN_LIMITS = {"loss_gap": 1e-3, "grad_gap": 1.5e-3, "change_gap": 3e-4}


def make_tiny(dst: pathlib.Path) -> pathlib.Path:
    shutil.copytree(ROOT / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        cfg.update(WIDTHS)
        cfg["vocab_size"] = 2000
        cfg["program"]["override"] = {**cfg["program"].get("override", {}),
                                      **OVERRIDE}
        if "engine" in cfg:
            cfg["engine"].update({"max_batch": 4, "num_pages": 200,
                                  "pages_per_seq_max": 24, "prompt_pad": 128,
                                  "admit_widths_warmed": [1, 2]})
        (dst / c["file"]).write_text(json.dumps(cfg))
    traffic = dst / "bench" / "traffic"
    for name in ("causal16k", "causal2k"):
        tr = json.loads((traffic / f"{name}.json").read_text())
        tr.update(rows=2, seq_len=256)
        (traffic / f"{name}.json").write_text(json.dumps(tr))
    for lim in (dst / "bench" / "limits").glob("qwen3-train.*.json"):
        limits = json.loads(lim.read_text())
        for name, limit in TRAIN_LIMITS.items():
            limits[name]["limit"] = limit
        lim.write_text(json.dumps(limits))
    tr = json.loads((traffic / "chat.json").read_text())
    tr.update(rate_per_s=2.0, warmup_s=1, drain_s=60, check={"requests": 3},
              prompt={"median": 100, "sigma": 0.5, "min": 16, "max": 256},
              output={"median": 8, "sigma": 0.5, "min": 2, "max": 32})
    (traffic / "chat.json").write_text(json.dumps(tr))
    (dst / "BENCHMARK.json").write_text(json.dumps(bench))
    return dst
