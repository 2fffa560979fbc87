"""Walks over a config's ``layers`` table (see ``configs/*.json``).

A table is a list of nodes: ``{"op": "conv", "name", "in_ch", "in_hw",
"out_ch", "kernel", "stride", "bn", "relu"}``, ``{"op": "pool", "name",
"kind", "size", "stride"}`` or ``{"op": "branch", "name", "merge", "relu",
"paths": [[node, ...], ...]}`` (an empty path is the identity).  After
the last node come global average pooling and one linear head.
"""
from __future__ import annotations

from typing import Iterator, List, Optional


def iter_convs(layers: List[dict]) -> Iterator[dict]:
    """Every conv of a table, in the order the program visits them."""
    for node in layers:
        if node["op"] == "conv":
            yield node
        elif node["op"] == "branch":
            for path in node["paths"]:
                yield from iter_convs(path)


def final_channels(layers: List[dict], ch: Optional[int] = None) -> int:
    """Channels that reach the head."""
    for node in layers:
        if node["op"] == "conv":
            ch = node["out_ch"]
        elif node["op"] == "branch":
            outs = [final_channels(p, ch) for p in node["paths"]]
            ch = sum(outs) if node["merge"] == "concat" else outs[0]
    return ch


def conv_out_hw(node: dict) -> int:
    return -(-node["in_hw"] // node["stride"])
