"""Bytes a label-kernel call must move, from its shapes.

The label kernel (``jit__labels_jax``) takes a ``(P, m)`` genome bit matrix
(one byte per bit), the graph's int32 tables (node ids, chain-edge nodes and
ids, extra-edge endpoints and ids) and writes ``(P, n)`` int32 labels.  It
does integer compares and scatter-mins only, so its roofline is the bytes
bound: no published integer peak of the chip applies to it.
"""
from __future__ import annotations

INT32 = 4


def genome_bytes(n: int, m: int, bit_bytes: int = 1) -> int:
    """Bytes per genome scored: its ``m`` bits in, its ``n`` labels out."""
    return m * bit_bytes + n * INT32


def table_bytes(n: int, chain: int, extra: int) -> int:
    """Bytes of the graph tables, read once per call: node ids, chain-edge
    nodes and ids, extra-edge endpoints and ids."""
    return (n + 2 * chain + 3 * extra) * INT32


def label_kernel_bytes(p: int, n: int, m: int, chain: int, extra: int
                       ) -> int:
    """Least bytes one call over ``p`` genomes moves between HBM and the
    core (``n`` nodes, ``m`` edges of which ``chain`` join consecutive
    nodes and ``extra`` do not)."""
    return p * genome_bytes(n, m) + table_bytes(n, chain, extra)


def graph_edge_counts(nodes: list, fields: list) -> tuple:
    """(n, m, chain, extra) of a configuration's layer table: an edge is a
    chain edge when it joins consecutive nodes of the table."""
    name_at = fields.index("name")
    inputs_at = fields.index("inputs")
    idx = {row[name_at]: i for i, row in enumerate(nodes)}
    edges = set()
    for v, row in enumerate(nodes):
        for src in row[inputs_at]:
            edges.add((idx[src], v))
    chain = sum(1 for u, v in edges if v == u + 1)
    return len(nodes), len(edges), chain, len(edges) - chain
