"""Roofline rows for the benchmark run, all analytic (shape arithmetic, no
compile and no device): the per-(arch x shape) compute / HBM / collective
terms from ``repro.launch.analytic`` over the v5e peaks in
``repro.launch.dryrun``.  The compiled full-mesh sweep is
`python -m repro.launch.dryrun --all`, run as its own process.

Also reports the sketch->Gram hot path's arithmetic intensity, fused
(``kernels/sketch_gram.py``, A streams once and A_tilde stays in VMEM)
next to the unfused two-pass pipeline it replaces (apply writes A_tilde to
HBM, Gram reads it back) — the HBM-traffic delta is the whole point of the
fusion, so it belongs on the roofline."""
from __future__ import annotations

from repro.launch import analytic
from repro.launch.dryrun import PEAK_FLOPS, HBM_BW, ICI_BW
from repro.models.registry import SHAPES, get_bundle, get_config


def sketch_gram_intensity(k: int, n: int, d: int, b: int):
    """Analytic per-path (flops, hbm_bytes) for the sketch->Gram hot path.

    Returns ``{"fused": (flops, bytes), "unfused": (flops, bytes),
    "d_tiles": t}`` for the d-tiled fused kernel vs the two-kernel
    apply+gram pipeline.  Both build on the same MXU primitives — encode
    matmul 2*K*n*b*d (one-hot / Hadamard mix columns are materialized in
    VMEM, not read from HBM) plus Gram 2*K*b*d^2 — but trade opposite
    resources:

    * unfused reads A once per block, writes the (K, b, d) A_tilde to HBM
      and reads it back for the Gram pass (2 extra round-trips).
    * fused never materializes A_tilde; with t = ceil(d_pad / d_tile)
      output tiles it recomputes the encode matmul (2t - 1)x (diagonal
      programs contract one panel with itself) but re-reads A's column
      panels 2t x — the diagonal programs still FETCH both panel blocks
      even though the second matmul is skipped (t = 1, the single-tile
      grid, recovers read-once / compute-once exactly).
    """
    from repro.kernels.sketch_gram import pick_d_tile

    d_pad = d + ((-d) % 128)
    t = max(1, -(-d_pad // pick_d_tile(b, d)))
    recompute = 2.0 * t - 1.0
    reread = 1.0 if t == 1 else 2.0 * t
    encode_fl, gram_fl = 2.0 * k * n * b * d, 2.0 * k * b * d * d
    a_read = 4.0 * k * n * d
    gram_out = 4.0 * d * d
    return {
        "fused": (encode_fl * recompute + gram_fl,
                  a_read * reread + gram_out),
        "unfused": (encode_fl + gram_fl,
                    a_read + 2.0 * 4.0 * k * b * d + gram_out),
        "d_tiles": t,
    }


def run(quick: bool = True):
    rows = []
    # sketch->gram hot path (paper Alg. 2): fused vs unfused AI at the
    # kernels_bench full shape (single-tile regime) AND at a d past the
    # single-tile VMEM budget, where the d-tiled grid trades encode
    # recompute + A re-reads against A_tilde round-trips.  Analytic, so
    # quick == full.
    ridge = PEAK_FLOPS / HBM_BW
    for kk, nn, dd, bb, suffix in ((10, 20_000, 512, 512, ""),
                                   (10, 20_000, 4096, 512, "_bigd")):
        cell = sketch_gram_intensity(kk, nn, dd, bb)
        tiles = cell["d_tiles"]
        for tag in ("fused", "unfused"):
            flops, byts = cell[tag]
            ai = flops / byts
            bound = "compute" if ai >= ridge else "memory"
            t_hbm = byts / HBM_BW
            t_mxu = flops / PEAK_FLOPS
            path = ("fused_tiled" if tiles > 1 else "fused") \
                if tag == "fused" else "unfused"
            rows.append({
                "name": f"roofline_sketch_gram_{tag}{suffix}",
                "us": max(t_hbm, t_mxu) * 1e6,
                "path": path,
                "derived": (f"bound={bound};ai={ai:.1f};ridge={ridge:.1f};"
                            f"hbm_mb={byts/1e6:.1f};gflop={flops/1e9:.1f};"
                            f"d_tiles={tiles};"
                            f"shape=({kk},{nn},{dd},{bb})"),
            })
    archs = ["qwen3-4b", "qwen3-moe-235b-a22b", "mamba2-780m"] if quick else \
        None
    if archs is None:
        from repro.configs import ASSIGNED_ARCHS
        archs = list(ASSIGNED_ARCHS)
    for arch in archs:
        cfg = get_config(arch)
        bundle = get_bundle(arch)
        for shape_name, shape in SHAPES.items():
            ok, _ = bundle.supports(shape)
            if not ok:
                continue
            costs = analytic.cell_costs(cfg, shape, 256)
            terms = {
                "c": costs.flops_per_chip / PEAK_FLOPS,
                "m": costs.hbm_bytes_per_chip / HBM_BW,
                "x": costs.coll_bytes_per_chip / ICI_BW,
            }
            bound = max(terms, key=terms.get)
            step = max(terms.values())
            rows.append({
                "name": f"roofline_{arch}_{shape_name}",
                "us": step * 1e6,
                "derived": (f"bound={bound};c_ms={terms['c']*1e3:.2f};"
                            f"m_ms={terms['m']*1e3:.2f};"
                            f"x_ms={terms['x']*1e3:.2f}"),
            })
    return rows
