"""The plain reference: semi-naive Datalog evaluation in numpy.

It imports nothing of the program under test and parses the rule text
itself.  Positive Datalog has one least model, so evaluating every rule
in every round (no strata, no planner, no sharding) gives the answer the
engine has to reproduce.  Relations are sorted unique int64 keys: a
binary fact ``(a, b)`` packs to ``a << 32 | b``, which keeps the
lexicographic order of rows for ids below 2**31.
"""

from __future__ import annotations

import re

import numpy as np

_ATOM = re.compile(r"([A-Za-z_][\w:.\-]*)\s*\(([^)]*)\)")


def parse_rules(lines) -> list[tuple]:
    """``["A(x, y), B(y, z) -> H(x, z)", ...]`` -> ``[(head, body)]`` with
    atoms ``(predicate, terms)``; a term is a variable name or an int id."""
    rules = []
    for line in lines:
        line = line.split("#", 1)[0].strip()
        if not line:
            continue
        lhs, sep, rhs = line.partition("->")
        if not sep:
            raise ValueError(f"rule without '->': {line!r}")
        body = [_atom(m) for m in _ATOM.finditer(lhs)]
        heads = [_atom(m) for m in _ATOM.finditer(rhs)]
        if len(heads) != 1 or not body:
            raise ValueError(f"expected one head and a body: {line!r}")
        rules.append((heads[0], tuple(body)))
    return rules


def _atom(m) -> tuple:
    terms = []
    for t in (s.strip() for s in m.group(2).split(",")):
        if t.isdigit():
            terms.append(int(t))
        elif re.fullmatch(r"[A-Za-z_]\w*", t):
            terms.append(t)
        else:
            raise ValueError(f"unsupported term {t!r} in {m.group(0)!r}")
    return m.group(1), tuple(terms)


def pack(rows: np.ndarray) -> np.ndarray:
    rows = np.asarray(rows, dtype=np.int64)
    if rows.ndim == 1:
        rows = rows.reshape(-1, 1)
    if rows.shape[1] == 1:
        return rows[:, 0].copy()
    if rows.shape[1] == 2:
        return (rows[:, 0] << 32) | rows[:, 1]
    raise ValueError(f"arity {rows.shape[1]} is not supported")


def unpack(keys: np.ndarray, arity: int) -> np.ndarray:
    if arity == 1:
        return keys.reshape(-1, 1)
    return np.stack([keys >> 32, keys & 0xFFFFFFFF], axis=1)


def _member(keys: np.ndarray, sorted_keys: np.ndarray) -> np.ndarray:
    if not sorted_keys.shape[0]:
        return np.zeros(keys.shape[0], bool)
    idx = np.minimum(np.searchsorted(sorted_keys, keys), sorted_keys.shape[0] - 1)
    return sorted_keys[idx] == keys


def _scan(atom, keys, arity) -> dict:
    """Bindings ``{var: column}`` of the rows of one atom."""
    rows = unpack(keys, arity)
    ok = np.ones(rows.shape[0], bool)
    first: dict = {}
    for pos, t in enumerate(atom[1]):
        if isinstance(t, int):
            ok &= rows[:, pos] == t
        elif t in first:
            ok &= rows[:, pos] == rows[:, first[t]]
        else:
            first[t] = pos
    return {v: rows[ok, pos] for v, pos in first.items()}


def _key(cols) -> np.ndarray:
    key = cols[0]
    for c in cols[1:]:
        key = (key << 32) | c
    return key


def _join(left: dict, right: dict) -> dict:
    shared = [v for v in left if v in right]
    n_left = len(next(iter(left.values())))
    n_right = len(next(iter(right.values())))
    if not shared:
        li = np.repeat(np.arange(n_left), n_right)
        ri = np.tile(np.arange(n_right), n_left)
    else:
        lk = _key([left[v] for v in shared])
        rk = _key([right[v] for v in shared])
        order = np.argsort(rk, kind="stable")
        rk = rk[order]
        lo = np.searchsorted(rk, lk, side="left")
        hi = np.searchsorted(rk, lk, side="right")
        counts = hi - lo
        li = np.repeat(np.arange(n_left), counts)
        starts = np.repeat(lo - (np.cumsum(counts) - counts), counts)
        ri = order[starts + np.arange(li.shape[0])]
    out = {v: c[li] for v, c in left.items()}
    for v, c in right.items():
        out.setdefault(v, c[ri])
    return out


def _fire(head, body, sources, arity) -> np.ndarray:
    """Packed head keys derived from ``body`` over ``sources[j]`` (the
    key array that atom ``j`` reads)."""
    todo = list(range(len(body)))
    j = min(todo, key=lambda k: sources[k].shape[0])
    todo.remove(j)
    bind = _scan(body[j], sources[j], arity[body[j][0]])
    while todo and len(next(iter(bind.values()), ())):
        nxt = next((k for k in todo if set(bind) & set(body[k][1])), todo[0])
        todo.remove(nxt)
        bind = _join(bind, _scan(body[nxt], sources[nxt], arity[body[nxt][0]]))
    n = len(next(iter(bind.values()), ()))
    if not n:
        return np.zeros(0, np.int64)
    cols = [
        np.full(n, t, np.int64) if isinstance(t, int) else bind[t]
        for t in head[1]
    ]
    return pack(np.stack(cols, axis=1))


def materialise(facts: dict, rules, max_rounds: int | None = None):
    """Least model of ``facts`` under ``rules``.

    Returns ``({pred: sorted unique (n, arity) int64 rows}, rounds)``,
    where ``rounds`` counts the rounds that derived a new fact.  With
    ``max_rounds`` the evaluation stops after that many such rounds."""
    arity: dict[str, int] = {}
    for p, rows in facts.items():
        rows = np.asarray(rows)
        arity[p] = 1 if rows.ndim == 1 else rows.shape[1]
    for head, body in rules:
        for atom in (head, *body):
            arity.setdefault(atom[0], len(atom[1]))
    empty = np.zeros(0, np.int64)
    full = {p: empty for p in arity}
    for p, rows in facts.items():
        full[p] = np.unique(pack(rows))
    delta = dict(full)
    rounds = 0
    while any(d.shape[0] for d in delta.values()):
        if max_rounds is not None and rounds >= max_rounds:
            break
        derived: dict[str, list] = {}
        for head, body in rules:
            for i, atom in enumerate(body):
                if not delta[atom[0]].shape[0]:
                    continue
                sources = [
                    delta[a[0]] if j == i else full[a[0]]
                    for j, a in enumerate(body)
                ]
                derived.setdefault(head[0], []).append(
                    _fire(head, body, sources, arity)
                )
        delta = {p: empty for p in arity}
        for p, parts in derived.items():
            cand = np.unique(np.concatenate(parts))
            new = cand[~_member(cand, full[p])]
            if new.shape[0]:
                delta[p] = new
                full[p] = np.insert(full[p], np.searchsorted(full[p], new), new)
        if any(d.shape[0] for d in delta.values()):
            rounds += 1
    return {p: unpack(k, arity[p]) for p, k in full.items()}, rounds


def compare(got: dict, want: dict) -> tuple[int, int]:
    """``(missing, extra)``: facts of ``want`` absent from ``got``, and
    facts of ``got`` absent from ``want``, over every predicate."""
    missing = extra = 0
    for p in set(got) | set(want):
        g = np.unique(pack(got[p])) if p in got else np.zeros(0, np.int64)
        w = np.unique(pack(want[p])) if p in want else np.zeros(0, np.int64)
        missing += int((~_member(w, g)).sum())
        extra += int((~_member(g, w)).sum())
    return missing, extra
