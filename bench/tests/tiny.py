"""Tiny stand-ins of the benchmark's cells, for the CPU tests: the same
files, harness and program path, at sizes a test run holds."""
from __future__ import annotations

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from lib import harness  # noqa: E402

TINY_CFG = {
    "smollm-360m": {"hidden_size": 64, "intermediate_size": 128,
                    "num_hidden_layers": 2, "num_attention_heads": 4,
                    "num_key_value_heads": 2, "vocab_size": 256},
    "paper-femnist-cnn": {"conv_channels": [4, 8], "fc": [16],
                          "num_classes": 10},
}
TINY_TRAFFIC = {
    "smollm360m.silo": {"examples_per_client": 8, "seq": 16,
                        "meta_batch": 4},
    "smollm360m.xdev_int8": {"population": 12, "examples_per_client": 4,
                             "cohort": 4, "seq": 16},
    "femnist_cnn.xdev": {"population": 24, "samples_mean": 20.0,
                         "samples_spread": 6.0, "samples_min": 4,
                         "cohort": 4, "cohort_chunk": 2, "client_batch": 12,
                         "local_epochs": 2, "meta_batch": 8},
}


# Limits of the stand-ins, from CPU readings at these sizes, where the
# program and the reference both compute in full fp32: a sound program
# reads at most 4.5e-6 on any number (int8 rounding ties), the bfloat16
# control at least 4e-4 on loss_gap, half the cohort left out at least
# 6e-3 on loss_gap, a state left unchanged about 1 on change_gap.  The
# cells' own limits are set on the chip at their own sizes.
TINY_LIMITS = {"loss_gap": 1e-4, "grad_gap": 1e-4, "change_gap": 1e-4}


def tiny_cell(name: str) -> "harness.Cell":
    cell = harness.find_cell(name, ROOT)
    cfg_name = cell.cfg["name"]
    cell.cfg = {**cell.cfg, **TINY_CFG[cfg_name]}
    cell.traffic = {**cell.traffic, **TINY_TRAFFIC[name],
                    "limits": dict(TINY_LIMITS)}
    return cell
