"""Recompute the `roofline` block of existing dry-run JSONs in place
(model-flops formula changes don't need recompiles)."""

import glob
import json
import sys

sys.path.insert(0, "src")

from repro.launch import roofline as rl
from repro.launch.specs import Cell
from repro.models import get_arch


def main(pattern="experiments/dryrun/*.json"):
    for path in sorted(glob.glob(pattern)):
        r = json.load(open(path))
        if r.get("status") != "ok":
            continue
        cell_d = {k: v for k, v in r["cell"].items()}
        cell = Cell(**cell_d)
        if cell.kind.startswith("gp_"):
            from repro.configs.gp_exact_1m import CONFIG as cfg
            if r.get("gp_mode"):
                cfg = cfg._replace(mode=r["gp_mode"])
            cfg = cfg._replace(compute_dtype=r.get("compute_dtype"))
        else:
            cfg = get_arch(cell.arch)
        mf = rl.model_flops_for(cfg, cell)
        # the dtype rule of repro.launch.dryrun
        cdt = getattr(cfg, "compute_dtype", "bf16") or "float32"
        roof = rl.analyze(r["cost"], {"total": r["collectives"]["total"]},
                          mf, r["n_devices"], compute_dtype=cdt)
        r["roofline"] = roof._asdict()
        json.dump(r, open(path, "w"), indent=1, default=str)
        print(f"{path.split('/')[-1]}: useful={roof.useful_ratio:.3f} "
              f"bott={roof.bottleneck}")


if __name__ == "__main__":
    main(*sys.argv[1:])
