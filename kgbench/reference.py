"""The plain reference: basic-graph-pattern evaluation over the whole graph.

A copy, in the benchmark's own terms, of the program's host oracle
(`repro.engine.oracle.evaluate_bgp`): set semantics, every solution over
every variable of the template, over the whole unpartitioned graph. It
imports nothing of the program and takes nothing the program made: it
encodes the generator's string triples with its own dictionary.

A template is a list of ``(s, p, o)`` strings; a term that starts with
``?`` is a variable. Solutions are rows over the template's variables in
order of first appearance, the order the program reports them in.
"""
from __future__ import annotations

import numpy as np


def is_var(term: str) -> bool:
    """Whether a template term is a variable (``?X``)."""
    return term.startswith("?")


def template_vars(patterns) -> list[str]:
    """The template's variables, in order of first appearance."""
    out: list[str] = []
    for pat in patterns:
        for t in pat:
            if is_var(t) and t not in out:
                out.append(t)
    return out


class Graph:
    """The string triples encoded with the reference's own dictionary."""

    def __init__(self, striples):
        self.index: dict[str, int] = {}
        ids = [self.index.setdefault(t, len(self.index))
               for tr in striples for t in tr]
        self.terms = np.empty(len(self.index), dtype=object)
        self.terms[:] = list(self.index)
        self.triples = np.unique(np.asarray(ids, np.int64).reshape(-1, 3),
                                 axis=0)
        order = np.argsort(self.triples[:, 1], kind="stable")
        by_p = self.triples[order]
        cuts = np.flatnonzero(np.diff(by_p[:, 1])) + 1
        self._by_p = {int(b[0, 1]): b for b in np.split(by_p, cuts)}

    def id_of(self, term: str) -> int:
        """The term's id, or -1 when the graph does not hold it."""
        return self.index.get(term, -1)

    def subjects_of_type(self, rdf_type: str) -> list[str]:
        """Subjects ``x`` of ``(x, rdf:type, rdf_type)``, sorted by name."""
        m = self.scan((None, self.id_of("rdf:type"), self.id_of(rdf_type)))
        return sorted(self.terms[m[:, 0]].tolist())

    def scan(self, ids) -> np.ndarray:
        """Triples matching ``(s, p, o)`` ids (None = any; -1 = absent)."""
        if any(i is not None and i < 0 for i in ids):
            return np.empty((0, 3), np.int64)
        s, p, o = ids
        tr = self._by_p.get(p, np.empty((0, 3), np.int64)) \
            if p is not None else self.triples
        mask = np.ones(len(tr), bool)
        if s is not None:
            mask &= tr[:, 0] == s
        if o is not None:
            mask &= tr[:, 2] == o
        return tr[mask]

    def count_by(self, pattern, pos: int) -> dict[int, int]:
        """{id at position pos: matches} of a pattern whose term at pos is
        left open (the other constants and variables as written)."""
        ids = [None if (is_var(t) or i == pos) else self.id_of(t)
               for i, t in enumerate(pattern)]
        m = self._matches(pattern, ids)
        vals, counts = np.unique(m[:, pos], return_counts=True)
        return dict(zip(vals.tolist(), counts.tolist()))

    def _matches(self, pattern, ids) -> np.ndarray:
        m = self.scan(ids)
        first: dict[str, int] = {}
        for pos, t in enumerate(pattern):
            if is_var(t):
                if t in first:      # a variable repeated inside one pattern
                    m = m[m[:, first[t]] == m[:, pos]]
                else:
                    first[t] = pos
        return m

    def evaluate(self, patterns) -> np.ndarray:
        """(n, n_vars) int64 solutions, sorted and distinct."""
        qvars = template_vars(patterns)
        col = {v: i for i, v in enumerate(qvars)}
        scans = []
        for pat in patterns:
            ids = [None if is_var(t) else self.id_of(t) for t in pat]
            scans.append(self._matches(pat, ids))
        rows = np.full((1, len(qvars)), -1, np.int64)
        bound: set[str] = set()
        left = list(range(len(patterns)))
        while left:
            # the smallest pattern that shares a bound variable, else the
            # smallest: a plain greedy order that avoids cartesian blow-ups
            joined = [i for i in left
                      if bound & {t for t in patterns[i] if is_var(t)}]
            i = min(joined or left, key=lambda j: (len(scans[j]), j))
            left.remove(i)
            rows = _join(rows, scans[i], patterns[i], col, bound)
            bound |= {t for t in patterns[i] if is_var(t)}
            if not len(rows):
                break
        return np.unique(rows, axis=0) if len(rows) else \
            rows.reshape(0, len(qvars))

    def decode(self, rows: np.ndarray) -> np.ndarray:
        """Rows of ids as rows of term strings."""
        return self.terms[rows] if len(rows) else \
            np.empty(rows.shape, dtype=object)


def _join(rows, matches, pattern, col, bound) -> np.ndarray:
    slots: dict[str, int] = {}
    for pos, t in enumerate(pattern):
        if is_var(t) and t not in slots:
            slots[t] = pos
    shared = [(pos, col[v]) for v, pos in slots.items() if v in bound]
    new = [(pos, col[v]) for v, pos in slots.items() if v not in bound]
    if shared:
        base = int(max(rows.max(initial=0), matches.max(initial=0))) + 2
        mkey = _key([matches[:, p] for p, _ in shared], base)
        rkey = _key([rows[:, c] for _, c in shared], base)
        order = np.argsort(mkey, kind="stable")
        lo = np.searchsorted(mkey[order], rkey, side="left")
        hi = np.searchsorted(mkey[order], rkey, side="right")
        counts = hi - lo
        r_idx = np.repeat(np.arange(len(rows)), counts)
        offs = np.arange(counts.sum()) - np.repeat(np.cumsum(counts) - counts,
                                                   counts)
        m_idx = order[np.repeat(lo, counts) + offs]
    else:
        r_idx = np.repeat(np.arange(len(rows)), len(matches))
        m_idx = np.tile(np.arange(len(matches)), len(rows))
    if not new:
        return rows[np.unique(r_idx)]
    out = rows[r_idx]
    for pos, c in new:
        out[:, c] = matches[m_idx, pos]
    return out


def _key(cols, base: int) -> np.ndarray:
    key = np.zeros(len(cols[0]), np.int64)
    for c in cols:
        key = key * base + c
    return key
