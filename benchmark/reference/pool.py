"""The committed message pool: (signing root, hash_to_G2 point) pairs."""

import os

POOL_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "data", "message_pool.txt")


def load(path=POOL_PATH):
    """[(message bytes, affine G2 point)] in file order."""
    out = []
    with open(path) as f:
        for line in f:
            parts = line.split()
            if not parts:
                continue
            x0, x1, y0, y1 = (int(v, 16) for v in parts[1:5])
            out.append((bytes.fromhex(parts[0]), ((x0, x1), (y0, y1))))
    return out
