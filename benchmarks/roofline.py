"""§Roofline — three-term roofline from the dry-run artifacts, plus
the analytic roofline of the CC fluid step.

Per (arch x shape) on the single-pod mesh:
    compute    = HLO_FLOPs / peak_FLOPs            (per device)
    memory     = HLO_bytes / HBM_bw                (per device)
    collective = collective_bytes / link_bw        (per device)
plus MODEL_FLOPS = 6·N·D (6·N_active·D for MoE) and the useful-compute
ratio MODEL_FLOPS / HLO_FLOPs (catches remat/redundancy waste).

CC fluid step (``fluid_step_rows``): the link reductions and the
per-flow block are pure bandwidth shapes — a handful of adds per
element over many state vectors — so the step's roofline is the HBM
term: one read of every input vector + one write of every output per
dt.  The rows report the bytes-per-step the step moves at DC scale and
the implied ceiling on steps/sec, alongside the attention kernels'
measured cells.

Hardware constants (TPU v5e per assignment): 197 TFLOP/s bf16,
819 GB/s HBM, ~50 GB/s/link ICI.
"""

from __future__ import annotations

import glob
import json
import os

PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # B/s / chip
ICI_BW = 50e9                # B/s / link (per assignment)

ART = "artifacts/dryrun"


def _model_flops(arch: str, shape: str) -> float:
    """Analytic 6·N_active·D for the cell (D = tokens processed)."""
    from repro.configs import SHAPES, get_config
    cfg = get_config(arch)
    cell = SHAPES[shape]
    n_active = cfg.active_param_count()
    if cell.step == "train":
        tokens = cell.global_batch * cell.seq_len
        return 6.0 * n_active * tokens
    if cell.step == "prefill":
        tokens = cell.global_batch * cell.seq_len
        return 2.0 * n_active * tokens
    return 2.0 * n_active * cell.global_batch      # decode: 1 token/seq


def analyze_cell(path: str, n_chips: int = 256) -> dict | None:
    with open(path) as f:
        rec = json.load(f)
    if rec.get("skipped"):
        return None
    arch, shape = rec["arch"], rec["shape"]
    # artifact numbers are per-device (SPMD module)
    flops_dev = rec["flops_total"]
    bytes_dev = rec["bytes_accessed_total"]
    coll_dev = rec["collective_bytes_total"]
    t_comp = flops_dev / PEAK_FLOPS
    t_mem = bytes_dev / HBM_BW
    t_coll = coll_dev / ICI_BW
    terms = {"compute": t_comp, "memory": t_mem, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    mf = _model_flops(arch, shape)
    mf_dev = mf / n_chips
    bound = max(terms.values())
    return {
        "arch": arch, "shape": shape, "mesh": rec["mesh"],
        "compute_s": t_comp, "memory_s": t_mem, "collective_s": t_coll,
        "dominant": dominant,
        "model_flops_dev": mf_dev,
        "useful_ratio": mf_dev / flops_dev if flops_dev > 0 else 0.0,
        "roofline_fraction": (mf_dev / PEAK_FLOPS) / bound
        if bound > 0 else 0.0,
        "temp_gib": rec["memory"].get("temp_size_in_bytes", 0) / 2**30,
        "args_gib": rec["memory"].get("argument_size_in_bytes", 0) / 2**30,
    }


def build_table(mesh_dir: str = "pod16x16") -> list[dict]:
    rows = []
    for path in sorted(glob.glob(os.path.join(ART, mesh_dir, "*.json"))):
        r = analyze_cell(path)
        if r:
            rows.append(r)
    return rows


def to_markdown(rows: list[dict]) -> str:
    hdr = ("| arch | shape | compute s | memory s | collective s | "
           "dominant | useful | roofline frac | temp GiB |\n"
           "|---|---|---|---|---|---|---|---|---|\n")
    body = ""
    for r in rows:
        body += (f"| {r['arch']} | {r['shape']} | {r['compute_s']:.3e} | "
                 f"{r['memory_s']:.3e} | {r['collective_s']:.3e} | "
                 f"**{r['dominant']}** | {r['useful_ratio']:.2f} | "
                 f"{r['roofline_fraction']:.3f} | {r['temp_gib']:.2f} |\n")
    return hdr + body


def fluid_step_rows() -> list[dict]:
    """Analytic roofline cells for one fluid step at DC scale.

    Shapes follow the perf harness's scaling curve extrapolated to DC
    scale (10^5..10^6 flows).  Bytes are the f32 vectors the step moves
    per dt: three reduction passes with (3, 3, 2) channels over
    N = F*K*H rows into S = L+1 links, and 40 [F] per-flow vectors
    (generation, notification timer, reaction state).

    The bytes model is :func:`repro.fleet.plan.fluid_step_bytes` — the
    SAME formula the fleet planner balances shards with, imported so
    the two can never drift.
    """
    from repro.fleet.plan import fluid_step_bytes

    rows = []
    for F, K, H, L in [(1 << 17, 1, 6, 1 << 14), (1 << 20, 4, 6, 1 << 16)]:
        byts = fluid_step_bytes(F, K, H, L)
        flops = sum(c * F * K * H for c in (3, 3, 2)) + 60 * F
        t_mem = byts / HBM_BW
        t_comp = flops / PEAK_FLOPS
        rows.append({
            "kernel": "fluid_step",
            "shape": f"f{F}k{K}l{L}",
            "bytes_per_step": byts,
            "memory_s": t_mem,
            "compute_s": t_comp,
            "dominant": "memory" if t_mem >= t_comp else "compute",
            "steps_per_s_ceiling": 1.0 / max(t_mem, t_comp),
        })
    return rows


def cc_to_markdown(rows: list[dict]) -> str:
    hdr = ("| kernel | shape | MB/step | memory s | dominant | "
           "steps/s ceiling |\n|---|---|---|---|---|---|\n")
    body = ""
    for r in rows:
        body += (f"| {r['kernel']} | {r['shape']} | "
                 f"{r['bytes_per_step'] / 2**20:.1f} | "
                 f"{r['memory_s']:.3e} | **{r['dominant']}** | "
                 f"{r['steps_per_s_ceiling']:.3e} |\n")
    return hdr + body


def main() -> list[tuple]:
    rows = build_table()
    cc_rows = fluid_step_rows()
    os.makedirs("artifacts", exist_ok=True)
    with open("artifacts/roofline.md", "w") as f:
        f.write(to_markdown(rows))
        f.write("\n## CC fluid step (analytic)\n\n")
        f.write(cc_to_markdown(cc_rows))
    out = []
    for r in rows:
        out.append((f"roofline.{r['arch']}.{r['shape']}",
                    max(r["compute_s"], r["memory_s"],
                        r["collective_s"]) * 1e6,
                    f"dom={r['dominant']} frac={r['roofline_fraction']:.3f}"
                    f" useful={r['useful_ratio']:.2f}"))
    for r in cc_rows:
        out.append((f"roofline.cc.{r['kernel']}.{r['shape']}",
                    r["memory_s"] * 1e6,
                    f"dom={r['dominant']} "
                    f"ceil={r['steps_per_s_ceiling']:.2e}steps/s"))
    return out


if __name__ == "__main__":
    for row in main():
        print(",".join(str(x) for x in row))
