"""State-transition observatory: the measurement-and-oracle plane for
the state-transition tail and fork choice (ISSUE 18).

Three coupled pieces, each its own module:

  * ``stage_profile`` — zero-cost-when-disabled epoch-stage profiler
    (``LTPU_STATE_PROFILE=1``): per-stage wall ms + validator-op counts
    for every epoch-processing stage, SSZ hashing, and committee-cache
    builds, keyed (fork, stage, validator-count bucket), accumulated
    EWMA + log-bucket histograms exactly like the PR-12 kernel-profile
    registry and persisted beside it (``state_profile.json``).
  * ``state_diff`` — byte-exact epoch-boundary digests (sha256 over the
    dense balances / participation / justification-bits arrays) plus
    summary deltas, recorded per epoch into a bounded ring: the
    bit-for-bit oracle the device-vectorization work will diff against.
  * ``forkchoice_forensics`` — ``find_head`` explain captures (per-
    candidate weight breakdown: vote weight, proposer boost, viability)
    and a forensic record per head CHANGE (old/new head, common
    ancestor depth, swing weight, triggering attestation batches).

Surfaces: ``GET /lighthouse/state-profile``, ``GET
/lighthouse/forkchoice``, the ``state_profile`` /
``forkchoice_forensics`` incident-bundle sections — the BEFORE
baseline for the ROADMAP epoch-on-device item.
"""

from . import forkchoice_forensics, stage_profile, state_diff

__all__ = ["forkchoice_forensics", "stage_profile", "state_diff"]
