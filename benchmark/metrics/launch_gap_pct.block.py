"""Dispatcher (verify_service/service.py): the reader of
launch_gap_pct.gossip, over a block cell's window."""

import os

from harness import cells

read = cells.module(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "metrics", "launch_gap_pct.gossip").read
