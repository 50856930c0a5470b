#!/usr/bin/env python3
"""Write data/message_pool.txt: signing roots with their hash-to-G2 points.

    python3 benchmark/data/make_pool.py [count]

Each line holds a 32-byte message and H(m) = hash_to_G2(m) under the
proof-of-possession ciphersuite, as hex: msg x.c0 x.c1 y.c0 y.c1.  The
traffic generator draws its messages from this pool (the seed picks which
and in what order), so no run pays the pure-Python hash-to-curve; the
reference verdict reads the same points.  The file is committed; rerun
this only to grow the pool.
"""

import hashlib
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from reference import bls12_381 as B  # noqa: E402

DEFAULT_COUNT = 1024


def message(k):
    return hashlib.sha256(b"lighthouse-tpu benchmark signing root %d"
                          % k).digest()


def main(argv):
    count = int(argv[1]) if len(argv) > 1 else DEFAULT_COUNT
    path = os.path.join(HERE, "message_pool.txt")
    with open(path, "w") as f:
        for k in range(count):
            m = message(k)
            (x0, x1), (y0, y1) = B.hash_to_g2(m)
            f.write(" ".join([m.hex()] + [format(v, "x")
                                          for v in (x0, x1, y0, y1)]) + "\n")
    print(f"wrote {count} messages to {path}")


if __name__ == "__main__":
    main(sys.argv)
