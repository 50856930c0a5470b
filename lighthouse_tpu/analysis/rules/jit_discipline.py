"""jit-discipline: the compiled-program set stays closed and enumerable.

PR 6's contract: every production jit/pad site in ``crypto/tpu/``
routes through ``CachedKernel`` (AOT persistence) and ``ShapePlanner``
(canonical shapes), so the compiled-program set is total over real
traffic and a warm start deserializes everything.  This rule keeps the
refactors honest:

- ``jax.jit(...)`` anywhere in ``crypto/tpu/`` OUTSIDE
  ``compile_cache.py`` is flagged (CachedKernel's internal fallback is
  the one legitimate owner); the two deliberate plain-jit sites
  (``bls_validate_pk``, ``fp.to_mont_jit`` — raw un-planned shapes,
  documented in PR 6) are waivered, not silently allowed
- any NEW definition or call of ``_next_pow2`` outside
  ``compile_cache.py`` is flagged — the ad-hoc pow-2 pad ladder the
  planner replaced must not creep back in
- ``jnp.pad`` / ``np.pad`` sites in ``crypto/tpu/`` are flagged:
  batch padding is the planner's job; kernel-internal lane alignment
  (fp limb padding) is waivered with that justification
"""

import ast

from ..core import Rule, register_rule


@register_rule
class JitDiscipline(Rule):
    name = "jit-discipline"
    description = ("crypto/tpu jit/pad sites route through "
                   "CachedKernel/ShapePlanner; _next_pow2 is banned "
                   "outside compile_cache.py")

    def applies_to(self, relpath):
        return relpath.startswith("crypto/tpu/")

    def check(self, tree, relpath, lines):
        findings = []
        owner = relpath.endswith("compile_cache.py")
        for node in ast.walk(tree):
            if isinstance(node, ast.Call):
                dotted = self.dotted(node.func)
                cname = self.call_name(node)
                if dotted == "jax.jit" and not owner:
                    findings.append(self.finding(
                        relpath, node,
                        "plain jax.jit site — production kernels route "
                        "through CachedKernel/load_or_compile so the "
                        "AOT cache stays total (PR 6 invariant)", lines,
                    ))
                elif cname == "_next_pow2" and not owner:
                    findings.append(self.finding(
                        relpath, node,
                        "_next_pow2 call — ad-hoc pow-2 padding was "
                        "replaced by ShapePlanner; plan shapes through "
                        "the planner menu", lines,
                    ))
                elif dotted in ("jnp.pad", "np.pad", "numpy.pad",
                                "jax.numpy.pad"):
                    findings.append(self.finding(
                        relpath, node,
                        f"{dotted} site — batch padding belongs to "
                        f"ShapePlanner (kernel-internal lane alignment "
                        f"needs a waiver saying so)", lines,
                    ))
            elif (isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                    and node.name == "_next_pow2" and not owner):
                findings.append(self.finding(
                    relpath, node,
                    "_next_pow2 reintroduced — compile_cache.py owns "
                    "the single implementation feeding the planner",
                    lines,
                ))
        return findings
