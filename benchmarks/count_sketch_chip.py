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
               panel columns) whose working set fits the kernel's budget;
  mxu ... live N/K  the kernel at the picked tiles with only N of the K
               blocks live, as the straggler mask leaves them: N is the
               most survivors whose ``OverSketchConfig`` provisions at
               most K blocks (118 of 148), the dead ones drawn from
               ``--seed``.  Its time against the
               all-live row's shows whether the cost follows the live
               blocks.

Each row is one JSON line: the case, the variant, the first call's
seconds (compile included), the seconds of each of ``--repeats`` timed
calls, their median in ms per sketch block of K, and for the kernel its
error against the segment sums, ``rel_max`` = max |diff| / max |segment
sums| and ``rel_fro`` (relative Frobenius), over its live blocks; the
masked row also gives ``dead_max``, the largest |entry| of a dead block,
which the kernel's contract makes 0.  ``--out`` writes the rows again as
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
    picked tiles, at them with the straggler mask's survivors live, and
    at each of ``tiles``."""
    interpret = jax.default_backend() != "tpu"
    kh, ks, ka, kl = jax.random.split(jax.random.PRNGKey(seed), 4)
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
    picked = count_sketch.pick_tiles(k, b, n, d)
    n_live = max(n for n in range(1, k + 1)
                 if sketch.OverSketchConfig(n * b, b).total_blocks <= k)
    some = jnp.zeros((k,), bool).at[
        jax.random.permutation(kl, k)[:n_live]].set(True)
    runs = [(picked, None)]
    if n_live < k:
        runs.append((picked, some))
    runs += [(t, None) for t in tiles if t != picked]
    for tile, live in runs:
        group, tn, td = tile
        variant = f"mxu {group}/{tn}/{td}"
        extra = {}
        if live is not None:
            variant += f" live {n_live}/{k}"
            extra["live_blocks"] = n_live
        vmem = count_sketch.vmem_bytes(group, b, tn, td)
        if vmem > count_sketch.VMEM_BUDGET_BYTES:
            rows.append({**case, "variant": variant, "skipped":
                         f"{vmem} bytes of VMEM, over the budget"})
            continue
        mask = jnp.ones((k,), bool) if live is None else live
        apply = functools.partial(
            count_sketch._count_sketch_apply, block_size=b, group=group,
            tile_n=tn, tile_d=td, interpret=interpret)
        out, first, times = _timed(lambda: apply(h, sigma, a, mask),
                                   repeats)
        # The error over the live blocks; a dead block must read 0.
        on = mask[:, None, None]
        ref = jnp.where(on, expect, 0.0)
        diff = jnp.where(on, out, 0.0) - ref
        if live is not None:
            extra["dead_max"] = float(jnp.abs(jnp.where(on, 0.0, out)).max())
        rows.append(row(variant, first, times, picked=tile == picked,
                        vmem_bytes=vmem,
                        rel_max=float(jnp.abs(diff).max()
                                      / jnp.abs(ref).max()),
                        rel_fro=float(jnp.linalg.norm(diff)
                                      / jnp.linalg.norm(ref)),
                        **extra))
        del out, diff, ref
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
