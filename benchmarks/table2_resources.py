"""Paper Table 2: resource utilisation, two views.

(a) The paper's own numbers (estimation column + utilisation %) reproduced
    from the Spartan-7 capacity figures — validates our FpgaSpec data.
(b) The TPU adaptation: per-kernel VMEM working set vs a 64 MiB budget and
    the model/cache bytes-per-device from the dry-run — the "does it fit"
    question Table 2 answers, asked of our target hardware.
"""

import json
from pathlib import Path

from repro.core.timing_model import (PAPER_RESOURCE_ESTIMATION,
                                     PAPER_RESOURCE_UTILISATION, SPARTAN7)

_DRYRUN = Path(__file__).resolve().parents[1] / "experiments" / "dryrun"


def run():
    rows = []
    # (a) paper's utilisation reproduced from capacities
    caps = {"LUT": "luts", "LUTRAM": "lutram", "BRAM": "bram", "DSP": "dsp"}
    for fpga, spec in SPARTAN7.items():
        derived = []
        for res, attr in caps.items():
            est = PAPER_RESOURCE_ESTIMATION[res]
            util = 100.0 * est / getattr(spec, attr)
            paper = PAPER_RESOURCE_UTILISATION[fpga][res]
            derived.append(f"{res}={util:.1f}%(paper {paper}%)")
        rows.append({"name": f"table2/fpga_{fpga}", "us_per_call": 0.0,
                     "derived": " ".join(derived)})

    # (b) TPU: Pallas kernel VMEM working sets (paper model + LM tiles)
    f32 = 4
    lstm_seq = (6 * 1 + 2 * 21 * 20 + 4 * 21 * 20 + 4 * 20) * f32 * 128  # block_b=128
    ssd = (128 * 64 + 128 * 128 + 2 * 128 * 128 + 64 * 128) * f32
    budget = 64 * 2 ** 20
    for name, bytes_ in [("lstm_sequence", lstm_seq), ("ssd_scan_tile", ssd)]:
        rows.append({
            "name": f"table2/vmem_{name}", "us_per_call": 0.0,
            "derived": f"working_set={bytes_/1024:.1f}KiB "
                       f"of_64MiB_vmem={100*bytes_/budget:.2f}%",
        })

    # per-device HBM from dry-run records, if the sweep has run
    summary = _DRYRUN / "summary.json"
    if summary.exists():
        recs = [r for r in json.loads(summary.read_text())
                if r.get("status") == "ok" and r.get("mesh") == "16x16"]
        worst = sorted(recs, key=lambda r: -r.get("bytes_per_device", 0))[:5]
        for r in worst:
            rows.append({
                "name": f"table2/hbm_{r['arch']}_{r['shape']}",
                "us_per_call": 0.0,
                "derived": f"bytes_per_device={r['bytes_per_device']/1e9:.2f}GB "
                           f"of_16GB={100*r['bytes_per_device']/16e9:.0f}%",
            })
    return rows
