"""The benchmark's own copy of the arithmetic it judges the program by.

Everything here is copied from the program on purpose and must not import
it: the seeded MixInstruct-style query generator, the byte tokenizer, the
behavioural member simulator, the Kaplan cost model and the fusion-prompt
layout.  A later change to the program's copies therefore cannot move what
the benchmark sends or what it calls correct.
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import Callable, Dict, List, Sequence

import numpy as np

# ---------------------------------------------------------------------------
# byte tokenizer: ids 0..255 are bytes, specials follow
# ---------------------------------------------------------------------------

PAD_ID, BOS_ID, EOS_ID, SEP_ID, CLS_ID = 256, 257, 258, 259, 260


def encode(text: str) -> List[int]:
    return list(text.encode("utf-8", errors="replace"))


def decode_capped(ids: Sequence[int], cap: int) -> str:
    """Decode at most ``cap`` byte tokens, dropping a UTF-8 sequence the
    cut leaves incomplete."""
    raw = bytes(i for i in ids if 0 <= i < 256)[:max(cap, 0)]
    for k in range(1, min(4, len(raw)) + 1):
        b = raw[-k]
        if b < 0x80:
            break
        if b >= 0xC0:
            need = 2 if b < 0xE0 else 3 if b < 0xF0 else 4
            if need > k:
                raw = raw[:-k]
            break
    return raw.decode("utf-8", errors="replace")


def pad_row(ids: Sequence[int], length: int) -> np.ndarray:
    out = np.full((length,), PAD_ID, np.int32)
    ids = list(ids)[:length]
    out[:len(ids)] = ids
    return out


# ---------------------------------------------------------------------------
# queries: eight instruction domains with rule-computable references
# ---------------------------------------------------------------------------

_WORDS = (
    "apple river stone cloud tiger maple ember quartz violet breeze "
    "copper meadow falcon harbor indigo jasmine kernel lantern marble nectar"
).split()


def _d_echo(rng):
    w = " ".join(rng.choice(_WORDS, rng.integers(2, 5)))
    return f"Repeat exactly: {w}", w


def _d_upper(rng):
    w = " ".join(rng.choice(_WORDS, rng.integers(2, 4)))
    return f"Uppercase this text: {w}", w.upper()


def _d_reverse(rng):
    w = str(rng.choice(_WORDS))
    return f"Reverse the word: {w}", w[::-1]


def _d_sort(rng):
    digits = "".join(map(str, rng.integers(0, 10, rng.integers(4, 8))))
    return f"Sort the digits ascending: {digits}", "".join(sorted(digits))


def _d_add(rng):
    a, b = int(rng.integers(10, 99)), int(rng.integers(10, 99))
    return f"What is {a} plus {b}?", str(a + b)


def _d_max(rng):
    xs = rng.integers(10, 99, 3)
    return f"Which is largest: {xs[0]}, {xs[1]} or {xs[2]}?", str(int(xs.max()))


def _d_vowels(rng):
    w = str(rng.choice(_WORDS))
    return f"How many vowels are in '{w}'?", str(sum(c in "aeiou" for c in w))


def _d_initials(rng):
    ws = rng.choice(_WORDS, rng.integers(2, 5))
    return "First letter of each word: " + " ".join(ws), "".join(w[0] for w in ws)


DOMAINS: Dict[str, Callable] = {
    "echo": _d_echo, "upper": _d_upper, "reverse": _d_reverse, "sort": _d_sort,
    "add": _d_add, "max": _d_max, "vowels": _d_vowels, "initials": _d_initials,
}
DOMAIN_NAMES = list(DOMAINS)


@dataclasses.dataclass(frozen=True)
class Query:
    query: str
    reference: str
    domain: str
    domain_id: int


def generate_queries(n: int, rng: np.random.Generator) -> List[Query]:
    out = []
    for _ in range(n):
        di = int(rng.integers(0, len(DOMAIN_NAMES)))
        name = DOMAIN_NAMES[di]
        q, ref = DOMAINS[name](rng)
        out.append(Query(q, ref, name, di))
    return out


# ---------------------------------------------------------------------------
# pool members: Kaplan cost and the behavioural simulator
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Member:
    name: str
    params_b: float
    n_layer: int
    d_model: int
    competence: tuple


def pool_from_config(rows: Sequence[dict]) -> List[Member]:
    return [Member(r["name"], float(r["params_b"]), int(r["n_layer"]),
                   int(r["d_model"]), tuple(float(c) for c in r["competence"]))
            for r in rows]


def expected_tokens(m: Member, q: Query) -> float:
    return (len(q.reference) + 2) * (1.0 + 0.1 * (1.0 - float(np.mean(m.competence))))


def cost_matrix(pool: Sequence[Member], queries: Sequence[Query]) -> np.ndarray:
    """[Q, N] FLOPs c_i * t_i(q): (2 N + 2 n_layer n_ctx d_model) per token."""
    out = np.zeros((len(queries), len(pool)))
    for qi, q in enumerate(queries):
        n_ctx = len(q.query) + 8
        for mi, m in enumerate(pool):
            per_token = 2.0 * int(m.params_b * 1e9) + 2.0 * m.n_layer * n_ctx * m.d_model
            out[qi, mi] = per_token * float(expected_tokens(m, q))
    return out


_GARBLE = "xqzjvkw"


def _member_text(m: Member, q: Query, rng: np.random.Generator) -> str:
    if rng.uniform() < m.competence[q.domain_id]:
        resp = q.reference
        if rng.uniform() < 0.15:
            resp = resp + "."
        return resp
    mode = rng.integers(0, 3)
    if mode == 0:
        chars = list(q.reference)
        k = max(1, int(len(chars) * rng.uniform(0.3, 0.8)))
        for i in rng.choice(len(chars), size=min(k, len(chars)), replace=False):
            chars[i] = _GARBLE[int(rng.integers(0, len(_GARBLE)))]
        return "".join(chars)
    if mode == 1:
        return q.reference[:max(1, len(q.reference) // 2)]
    other = DOMAINS[DOMAIN_NAMES[int(rng.integers(0, len(DOMAIN_NAMES)))]]
    return other(rng)[1]


def sim_member_text(sim_seed: int, member_idx: int, m: Member, q: Query, cap: int) -> str:
    """What the behavioural simulator answers for one member and query."""
    digest = hashlib.blake2b(q.query.encode("utf-8", errors="replace"), digest_size=8).digest()
    rng = np.random.default_rng([sim_seed, member_idx, int.from_bytes(digest, "little")])
    return decode_capped(encode(_member_text(m, q, rng)), cap)


# ---------------------------------------------------------------------------
# prompts
# ---------------------------------------------------------------------------


def predictor_tokens(query: str, length: int) -> np.ndarray:
    return pad_row([CLS_ID] + encode(query), length)


def fusion_prompt(query: str, member_texts: Sequence[str], query_len: int,
                  member_cap: int, length: int) -> np.ndarray:
    """``query <sep> answer_1 <sep> ... <sep> answer_k``, each part cut to
    its cap, the whole cut and padded to ``length``."""
    parts = list(encode(query)[:query_len])
    for text in member_texts:
        parts += [SEP_ID] + encode(text)[:member_cap]
    return pad_row(parts, length)
