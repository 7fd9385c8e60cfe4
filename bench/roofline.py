"""Bytes and operations that a piece of work needs, counted from its shapes.

Each function counts what the algorithm must move or compute, not what the
program happens to do, so a faster implementation moves the share and never
the yardstick.  Peaks are in ``bench/peaks.json``, keyed by ``device_kind``.
"""
from __future__ import annotations

import json
import pathlib

__all__ = ["peaks", "vdt_walk_bytes"]

_PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; a kind not in it is an error."""
    table = json.loads(_PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {_PEAKS}")
    return table[device_kind]


def vdt_walk_bytes(n_leaves: int, n_blocks: int, cols: int, iters: int) -> int:
    """HBM bytes of ``iters`` eq.-15 steps of Algorithm 1 over ``cols`` float32 columns.

    Per step, with ``n_nodes = 2 * n_leaves - 1`` and ``n_blocks`` active
    blocks:

    * CollectUp reads the labels and writes every node sum T:
      ``(n_leaves + n_nodes) * cols`` words;
    * the block stage reads the block table (a, b, q: three words a block),
      gathers ``T[b]`` and adds into the per-node sums c:
      ``3 * n_blocks + 2 * n_blocks * cols`` words;
    * DistributeDown reads c and writes the leaves: ``(n_nodes + n_leaves) * cols``;
    * the eq.-15 update reads the seed labels and writes the new labels:
      ``2 * n_leaves * cols``, the product just written being read back
      within the same pass.

    Inactive block slots and padded columns are not counted: the walk does
    not need them.
    """
    n_nodes = 2 * n_leaves - 1
    words = (3 * n_blocks
             + cols * (2 * n_blocks + 2 * n_nodes + 4 * n_leaves))
    return 4 * words * iters
