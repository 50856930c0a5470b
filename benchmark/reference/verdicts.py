"""The plain reference verdict of a signature set.

A set (signature S, pubkeys P_1..P_k, message m) is valid when S lies in
G2 and e(P_1 + ... + P_k, H(m)) = e(G1, S).  Every pubkey the benchmark
makes is P_j = [s_j]G1 with a secret it knows, so by bilinearity and the
non-degeneracy of the pairing the set is valid exactly when
S = [s_1 + ... + s_k]H(m) (a point of that form is in G2).  That
comparison decides the same question as the pairing check, with one
scalar multiplication instead of two pairings, and shares no code with
the system under test.
"""

from . import bls12_381 as B


class Reference:
    """Verdicts by the known secrets, memoised per (secret, message,
    signature) since closed loops revisit their requests."""

    def __init__(self, pool):
        self.pool = pool
        self._memo = {}

    def set_valid(self, meta, signature):
        key = (meta.secret, meta.msg, signature)
        v = self._memo.get(key)
        if v is None:
            want = B.g2_mul(self.pool[meta.msg][1], meta.secret)
            v = self._memo[key] = signature == want
        return v

    def request(self, request):
        """Per-set verdicts of one request."""
        return [self.set_valid(m, s.signature)
                for m, s in zip(request.meta, request.sets)]
