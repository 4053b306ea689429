"""Roofline rows for the benchmark run, all analytic (shape arithmetic, no
compile and no device): the per-(arch x shape) compute / HBM / collective
terms from ``repro.launch.analytic`` over the v5e peaks in
``repro.launch.dryrun``.  The compiled full-mesh sweep is
`python -m repro.launch.dryrun --all`, run as its own process."""
from __future__ import annotations

from repro.launch import analytic
from repro.launch.dryrun import PEAK_FLOPS, HBM_BW, ICI_BW
from repro.models.registry import SHAPES, get_bundle, get_config


def run(quick: bool = True):
    rows = []
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
