#!/usr/bin/env python3
"""Time the count-sketch apply S^T A on one TPU chip, and check it.

    python3 benchmarks/count_sketch_chip.py [--n 200000] [--d 2000]
        [--cases 148x256,32x1024] [--tiles 16/512/512,8/1024/1024]
        [--repeats 3] [--seed S] [--out PATH]

For each case ``KxB`` it makes A (n x d, standard normal) and K count-sketch
blocks of width B on the device from ``--seed``, and times one call over
all K blocks of:

  segment_sum  ``core/sketch.py``'s ``lax.map`` of segment sums;
  mxu          ``kernels/count_sketch.py`` at the tiles ``pick_tiles``
               gives (``"picked": true``), then at each ``--tiles``
               setting ``G/tn/td`` (sketch blocks per group, panel rows,
               panel columns) whose working set fits the kernel's budget.

Each row is one JSON line: the case, the variant, the first call's
seconds (compile included), the seconds of each of ``--repeats`` timed
calls, their median in ms per sketch block, and for the kernel its error
against the segment sums, ``rel_max`` = max |diff| / max |segment sums|
and ``rel_fro`` (relative Frobenius).  ``--out`` writes the rows again as
one JSON list.  The defaults are epsilon's width and sketch (K = 148
blocks of b = 256).  Off a TPU the kernel runs in the Pallas interpreter,
which checks the rows at a small size and times nothing of interest.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(REPO, "src"), REPO]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import sketch  # noqa: E402
from repro.kernels import count_sketch  # noqa: E402


def parse_cases(text: str):
    """"148x256,32x1024" -> [(148, 256), (32, 1024)]: (K, b) pairs."""
    return [tuple(int(v) for v in c.split("x")) for c in text.split(",") if c]


def parse_tiles(text: str):
    """"16/512/512" -> [(16, 512, 512)]: (group, tile_n, tile_d)."""
    return [tuple(int(v) for v in t.split("/")) for t in text.split(",") if t]


def _timed(fn, repeats: int):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    first = time.perf_counter() - t0
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    return out, first, times


def measure(n: int, d: int, k: int, b: int, tiles, repeats: int,
            seed: int):
    """The rows of one case: the segment sums, then the kernel at the
    picked tiles and at each of ``tiles``."""
    interpret = jax.default_backend() != "tpu"
    kh, ks, ka = jax.random.split(jax.random.PRNGKey(seed), 3)
    h = jax.random.randint(kh, (k, n), 0, b, dtype=jnp.int32)
    sigma = jax.random.rademacher(ks, (k, n), dtype=jnp.float32)
    a = jax.random.normal(ka, (n, d), dtype=jnp.float32)
    case = {"n": n, "d": d, "blocks": k, "block_size": b}

    def row(variant, first, times, **extra):
        return {**case, "variant": variant, "first_s": first, "s": times,
                "ms_per_block": 1e3 * statistics.median(times) / k, **extra}

    segment_sums = jax.jit(functools.partial(sketch._apply_segment_sum,
                                             block_size=b))
    expect, first, times = _timed(lambda: segment_sums(h, sigma, a), repeats)
    rows = [row("segment_sum", first, times)]
    ref_max = jnp.abs(expect).max()
    ref_fro = jnp.linalg.norm(expect)
    picked = count_sketch.pick_tiles(k, b, n, d)
    for tile in [picked] + [t for t in tiles if t != picked]:
        group, tn, td = tile
        variant = f"mxu {group}/{tn}/{td}"
        vmem = count_sketch.vmem_bytes(group, b, tn, td)
        if vmem > count_sketch.VMEM_BUDGET_BYTES:
            rows.append({**case, "variant": variant, "skipped":
                         f"{vmem} bytes of VMEM, over the budget"})
            continue
        apply = functools.partial(
            count_sketch._count_sketch_apply, block_size=b, group=group,
            tile_n=tn, tile_d=td, interpret=interpret)
        out, first, times = _timed(lambda: apply(h, sigma, a), repeats)
        diff = out - expect
        rows.append(row(variant, first, times, picked=tile == picked,
                        vmem_bytes=vmem,
                        rel_max=float(jnp.abs(diff).max() / ref_max),
                        rel_fro=float(jnp.linalg.norm(diff) / ref_fro)))
        del out, diff
    return rows


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--n", type=int, default=200_000)
    p.add_argument("--d", type=int, default=2000)
    p.add_argument("--cases", default="148x256")
    p.add_argument("--tiles", default="")
    p.add_argument("--repeats", type=int, default=3)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)
    rows = []
    for k, b in parse_cases(args.cases):
        for r in measure(args.n, args.d, k, b, parse_tiles(args.tiles),
                         args.repeats, args.seed):
            print(json.dumps(r), flush=True)
            rows.append(r)
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform}))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
