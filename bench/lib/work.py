"""Operations and bytes of the round's Pallas kernels, from shapes.

The server and cohort kernels sweep one ``(rows, 128)`` fp32 buffer per
dtype group of the parameters, with rows padded to a multiple of 256.  The
bytes are the work each pass must move over that buffer (what it reads and
what it writes, once), not what an implementation happens to move:

* accumulate ``acc + w * g``, once per client and group: read acc, read g,
  write acc (12 B and 2 FLOP an element);
* int8 quantize ``q = round(g / s)``, once per client and group: read g,
  write q (5 B, 2 FLOP);
* int8 dequant-FMA ``acc + s * w * q``, once per client and group: read acc
  and q, write acc (9 B, 2 FLOP);
* the server update, once per group: SGD reads G and p and writes p (12 B,
  2 FLOP); Adam reads G, p, m, v and writes p, m, v (28 B, 11 FLOP).
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

LANES, ROW_ALIGN = 128, 256

PER_ELEMENT = {            # (FLOP, bytes) per element of the group buffer
    "accumulate": (2, 12),
    "quantize_i8": (2, 5),
    "dequant_i8_fma": (2, 9),
    "update_sgd": (2, 12),
    "update_adam": (11, 28),
}


def group_rows(abstract_params) -> List[int]:
    """Padded row count of each dtype group of the parameter tree."""
    import jax
    sizes: Dict[str, int] = {}
    for leaf in jax.tree.leaves(abstract_params):
        dt = str(leaf.dtype)
        sizes[dt] = sizes.get(dt, 0) + int(math.prod(leaf.shape))
    rows = []
    for n in sizes.values():
        r = -(-n // LANES)
        rows.append(-(-r // ROW_ALIGN) * ROW_ALIGN)
    return rows


def round_work(abstract_params, traffic: dict) -> Dict[str, Tuple[float,
                                                                  float]]:
    """(FLOP, bytes) a round asks of each kernel family."""
    elems = sum(r * LANES for r in group_rows(abstract_params))
    cohort = int(traffic["cohort"])
    out = {}

    def add(kind, calls):
        f, b = PER_ELEMENT[kind]
        out[kind] = (float(f * elems * calls), float(b * elems * calls))

    if traffic.get("codec", "none") == "int8":
        add("quantize_i8", cohort)
        add("dequant_i8_fma", cohort)
    else:
        add("accumulate", cohort)
    add("update_" + traffic["server_opt"], 1)
    return out
