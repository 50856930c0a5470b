#!/usr/bin/env python
"""Exact per-launch counts for the device BLS kernel: field multiplications
and sequential loop steps.

Traces batched_verify_kernel on CPU at a padded (sets, pubkeys) shape,
from `bls.example_chunk_args` (the prewarm's padding content: the traced
program depends on shapes only).  Pure host-side tracing — no TPU.

- Multiplications: fp.mont_mul wrapped by a counter; every traced call
  records (instances, lane-weighted mults), giving the M in the roofline
  bound  sets/s <= T_mult(B_eff) / M_per_set  (ROADMAP.md).  Static trace
  counts: a scan body traces once.
- Loop steps: the jaxpr walked with each scan's length multiplied through
  the scans that enclose it, so the totals are what one launch EXECUTES.
  On TPU every scan step is one device while-loop iteration.  Scans of
  length NLIMB are `fp._carry_scan`'s (is_zero/canonical); length 2N was
  mont_mul's carry scan before it was made scan-free; any other length is
  an outer loop (Miller loop, exponentiations, ladders).
- Column products, by form, in the same walk: `shift` is the
  `fp._mul_cols_shift` product (its one diagonal `reduce_sum` over a
  (N, 2N-1, *batch) operand), `const_dot` a `fp._mul_const_cols` dot
  against a Toeplitz constant (T_NP or T_P).  Each is counted executed
  and lane-weighted (lanes = its batch size); every mont_mul does two
  constant dots over its own lanes, so half of their counts are the
  executed mont_muls and their lane-weighted mults.

Usage: python tools/count_kernel_mults.py [sets pks]...
"""

import collections
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

from lighthouse_tpu.crypto.tpu import bls as tb  # noqa: E402
from lighthouse_tpu.crypto.tpu import fp  # noqa: E402


class MultCounter:
    def __init__(self):
        self.instances = 0
        self.mults = 0
        self._orig = fp.mont_mul

    def __enter__(self):
        def counted(a, b):
            self.instances += 1
            shape = np.broadcast_shapes(a.shape, b.shape)
            self.mults += int(np.prod(shape[1:])) if len(shape) > 1 else 1
            return self._orig(a, b)

        fp.mont_mul = counted
        return self

    def __exit__(self, *a):
        fp.mont_mul = self._orig


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for x in v if isinstance(v, (tuple, list)) else (v,):
            if hasattr(x, "jaxpr") and hasattr(x, "consts"):   # ClosedJaxpr
                yield x.jaxpr
            elif hasattr(x, "eqns"):                            # Jaxpr
                yield x


def _column_product(eqn):
    """('shift' | 'const_dot', lanes) when `eqn` is a column product of
    mont_mul's, else None."""
    name = eqn.primitive.name
    if name == "dot_general":
        lhs = eqn.invars[0].aval
        if lhs.shape in (fp.T_NP.shape, fp.T_P.shape) and \
                lhs.dtype == fp.T_P.dtype:
            return "const_dot", int(np.prod(eqn.outvars[0].aval.shape[1:]))
    elif name == "reduce_sum" and eqn.params["axes"] == (0,):
        shape = eqn.invars[0].aval.shape
        if shape[:2] == (fp.NLIMB, 2 * fp.NLIMB - 1):
            return "shift", int(np.prod(shape[2:]))
    return None


def loop_steps(jaxpr):
    """Executed work of one launch: ({scan length: [scans run, steps]},
    equations executed, while loops met, {column-product form:
    [products run, lane-weighted]}).  A while loop's trip count is
    unknown at trace time: its body counts once and it is reported."""
    by_len = collections.defaultdict(lambda: [0, 0])
    products = collections.defaultdict(lambda: [0, 0])
    totals = {"eqns": 0, "whiles": 0}

    def walk(j, mult):
        for eqn in j.eqns:
            totals["eqns"] += mult
            inner = mult
            if eqn.primitive.name == "scan":
                n = eqn.params["length"]
                by_len[n][0] += mult
                by_len[n][1] += mult * n
                inner = mult * n
            elif eqn.primitive.name == "while":
                totals["whiles"] += mult
            product = _column_product(eqn)
            if product:
                products[product[0]][0] += mult
                products[product[0]][1] += mult * product[1]
            for sub in _sub_jaxprs(eqn):
                walk(sub, inner)

    walk(jaxpr, 1)
    return dict(by_len), totals["eqns"], totals["whiles"], dict(products)


def count(n_sets, pks):
    args, _ = tb.example_chunk_args(n_sets, pks)
    with MultCounter() as mc:
        closed = jax.make_jaxpr(tb.batched_verify_kernel)(*args)
    return mc, closed.jaxpr


def report(n, m):
    mc, jaxpr = count(n, m)
    print(f"sets={n} pks={m}: traced mont_mul instances={mc.instances} "
          f"lane-weighted mults={mc.mults} per-set={mc.mults / n:.0f}")
    by_len, eqns, whiles, products = loop_steps(jaxpr)
    kinds = {fp.NLIMB: "carry (is_zero/canonical)",
             2 * fp.NLIMB: "carry (mont_mul)"}
    total = sum(s for _, s in by_len.values())
    print(f"  executed loop steps={total} equations={eqns} "
          f"while loops={whiles}")
    for length in sorted(by_len):
        runs, steps = by_len[length]
        print(f"  scan length {length:>4}: {runs:>8} runs {steps:>9} steps"
              f"  {kinds.get(length, 'outer')}")
    dots = products.get("const_dot", [0, 0])
    print(f"  executed mont_mul={dots[0] // 2} lane-weighted mults="
          f"{dots[1] // 2} per-set={dots[1] / 2 / n:.0f}")
    n_all = sum(runs for runs, _ in products.values())
    for form in sorted(products):
        runs, lanes = products[form]
        print(f"  column products {form:>9}: {runs:>8} executed "
              f"{lanes:>10} lane-weighted ({100 * runs / n_all:.1f} %)")


if __name__ == "__main__":
    shapes = [(32, 1), (32, 512)]
    if len(sys.argv) > 2:
        shapes = [(int(sys.argv[1]), int(sys.argv[2]))]
    for n, m in shapes:
        report(n, m)
