"""Repo-specific static analysis: the review invariants, machine-checked.

Five PRs of review hardening accumulated concurrency and
compile-discipline invariants that were enforced only by reviewer
memory ("WARNs emit OUTSIDE the service/processor locks", "every
production jit site routes through CachedKernel/ShapePlanner", "every
worker thread is daemon and watchdog-registered").  This package
encodes them as AST rules so the fused-SPMD and overlay refactors the
ROADMAP plans can't silently regress the dispatcher.

Layout:

- ``core.py``      — Finding/Rule plumbing, the per-file AST walk, the
                     waiver ledger (every waiver carries a mandatory
                     justification; stale waivers are findings too)
- ``rules/``       — one module per rule, registered via
                     ``@register_rule`` (the plugin seam: a new
                     invariant is one new module, no core change)
- ``waivers.json`` — the machine-readable waiver ledger

Entrypoints: ``tools/lint.py`` (CLI, nonzero exit on unwaived
findings) and ``tests/test_analysis.py`` (tier-1 wiring).
"""

from .core import (  # noqa: F401
    Finding,
    Rule,
    all_rules,
    analyze_source,
    analyze_sources,
    default_waivers_path,
    format_report,
    load_waivers,
    register_rule,
    run_analysis,
)

from . import rules  # noqa: F401  (importing registers every rule)
