"""Roofline aggregation: reads runs/dryrun/*.json into the EXPERIMENTS.md
tables (per arch x shape x mesh: three terms, dominant bottleneck,
MODEL_FLOPS ratio, fit)."""
from __future__ import annotations

import json
import pathlib

RUNS = pathlib.Path(__file__).resolve().parents[1] / "runs" / "dryrun"


def rows(mesh: str | None = "pod16x16", variant: str = "") -> list[dict]:
    out = []
    for f in sorted(RUNS.glob("*.json")):
        is_perf = "__perf" in f.name
        if bool(variant) != is_perf:
            continue
        r = json.loads(f.read_text())
        if mesh and r.get("mesh") != mesh:
            continue
        out.append(r)
    return out


def table(mesh: str = "pod16x16", variant: str = "") -> str:
    lines = ["arch,shape,status,compute_s,memory_s,collective_s,dominant,"
             "bytes_per_dev_GB,fits_16gb,useful_ratio,roofline_frac,"
             "model_gflops"]
    for r in rows(mesh, variant):
        if r["status"] != "ok":
            lines.append(f"{r['arch']},{r['shape']},{r['status']},,,,,,,,")
            continue
        ro, m = r["roofline"], r["memory"]
        lines.append(
            f"{r['arch']},{r['shape']},ok,"
            f"{ro['compute_s']:.3e},{ro['memory_s']:.3e},"
            f"{ro['collective_s']:.3e},{ro['dominant'].replace('_s','')},"
            f"{m['total_bytes']/1e9:.2f},{m['fits_16gb']},"
            f"{ro['useful_flops_ratio']:.3f},{ro['roofline_fraction']:.3f},"
            f"{ro['model_flops_global']/1e9:.0f}")
    return "\n".join(lines)


def main() -> None:
    for mesh in ("pod16x16", "pod2x16x16"):
        print(f"== mesh {mesh} ==")
        print(table(mesh))
    perf = table("pod16x16", variant="perf").splitlines()
    if len(perf) > 1:
        print("== §Perf hillclimb variants (pod16x16) ==")
        print("\n".join(perf))


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
