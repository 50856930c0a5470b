"""Device launch (crypto/tpu/compile_cache.py CachedKernel): the reader
of launch_ms_per_set.gossip, over a block cell's window."""

import os

from harness import cells

read = cells.module(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "metrics", "launch_ms_per_set.gossip").read
