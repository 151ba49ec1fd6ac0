"""Review-shaped data: a title and a body of free text, a polarity label.

The shape of the Amazon Review Polarity table (Zhang, Zhao, LeCun 2015):
two text fields and two balanced classes. Word types come from a FIXED
vocabulary (no seed: type ``i`` is the same string in every table) under a
Zipf law; a body's token count is log-normal, a title's a clipped
geometric. Text is written as text is: a separator of punctuation, an
apostrophe clitic, an underscore or a doubled space between tokens, some
tokens capitalised or in capitals, a share of types holding digits. Fixed
QUOTAS of rows, laid out by a seeded permutation, carry a non-ASCII
character, run past 4,000 characters, or have no title, so that every
table of ``n`` rows has the same number of each and the checked width is
the same on every seed and stream. The label sums the signed weights of a
fixed lexicon over the body's tokens and, doubled, the title's, adds
Gaussian noise, and cuts at the table's own median: the classes are exactly
balanced, as published. Everything is drawn in bulk with numpy: no Python a
token, one slice a row.
"""

from __future__ import annotations

import functools

import numpy as np

from chipbench.data import Table, seeded

#: what stands between two tokens, and how often (shares sum to 1)
SEPARATORS = ((" ", 0.70), (", ", 0.08), (". ", 0.07), ("  ", 0.03),
              ("'s ", 0.025), ("n't ", 0.015), ("_", 0.01), ("-", 0.02),
              ("! ", 0.02), ("? ", 0.005), (" (", 0.005), (") ", 0.005),
              ("; ", 0.005), (": ", 0.005), ("...", 0.005))
#: appended to a row of the non-ASCII quota, in turn
NON_ASCII = (" café", " “great”", " naïve", " don’t",
             " Über", " 5€")
#: rows rendered at a time: the index arrays of a chunk stay small enough
#: for the allocator to hand the same pages out again
_CHUNK_ROWS = 2_000


@functools.lru_cache(maxsize=2)
def vocabulary(size: int):
    """``(pool, start, length)``: the bytes of every word type end to end,
    and where each begins. Types are 2 to 12 lower-case letters, frequent
    types shorter; one type in fifty holds digits."""
    rng = np.random.default_rng(20150904)        # the vocabulary is fixed
    rank = np.arange(size)
    length = np.clip(2 + np.log2(rank + 2.0) * 0.45
                     + rng.normal(0.0, 1.5, size), 2, 12).astype(np.int64)
    start = np.zeros(size + 1, np.int64)
    np.cumsum(length, out=start[1:])
    pool = rng.integers(ord("a"), ord("z") + 1, size=int(start[-1]),
                        dtype=np.uint8)
    digits = np.nonzero(rng.uniform(size=size) < 0.02)[0]
    for off in (0, 1):                   # "4gb", "mp3", "10", "2nd"
        at = start[digits] + np.minimum(off, length[digits] - 1) \
            * (rng.uniform(size=digits.size) < 0.6)
        pool[at] = rng.integers(ord("0"), ord("9") + 1, size=digits.size,
                                dtype=np.uint8)
    return pool, start[:-1], length


@functools.lru_cache(maxsize=2)
def _zipf_cdf(size: int, exponent: float) -> np.ndarray:
    cdf = np.cumsum(np.arange(1, size + 1, dtype=np.float64) ** -exponent)
    return cdf / cdf[-1]


@functools.lru_cache(maxsize=2)
def lexicon(size: int, words: int, first: int, last: int) -> np.ndarray:
    """The signed weight of every word type: ``words`` types among ranks
    ``first`` to ``last`` weigh plus or minus 0.5 to 1.5, the rest 0."""
    rng = np.random.default_rng(19970717)        # the lexicon is fixed
    w = np.zeros(size)
    types = first + rng.choice(last - first, size=words, replace=False)
    w[types] = rng.choice([-1.0, 1.0], size=words) * rng.uniform(
        0.5, 1.5, size=words)
    return w


def _render(ids: np.ndarray, counts: np.ndarray, rng, vocab) -> list:
    """The strings of rows holding ``counts`` tokens each (``ids`` end to
    end): word bytes and separator bytes gathered into one buffer, case
    applied, one slice a row."""
    pool, wstart, wlen = vocab
    sep_bytes = np.frombuffer("".join(s for s, _ in SEPARATORS).encode(),
                              np.uint8)
    sep_len = np.array([len(s) for s, _ in SEPARATORS], np.int32)
    sep_start = (np.cumsum(sep_len) - sep_len + pool.size).astype(np.int32)
    wstart, wlen = wstart.astype(np.int32), wlen.astype(np.int32)
    both = np.concatenate([pool, sep_bytes])
    sep_cdf = np.cumsum([p for _, p in SEPARATORS])
    sep_cdf = sep_cdf[:-1] / sep_cdf[-1]
    out: list = []
    row_end = np.cumsum(counts)
    for lo in range(0, counts.size, _CHUNK_ROWS):
        hi = min(lo + _CHUNK_ROWS, counts.size)
        t0 = int(row_end[lo - 1]) if lo else 0
        tok = ids[t0:int(row_end[hi - 1])]
        last = np.zeros(tok.size, bool)
        last[row_end[lo:hi] - t0 - 1] = True     # no separator ends a row
        sep = np.searchsorted(sep_cdf, rng.uniform(size=tok.size))
        wl = wlen[tok]
        sl = np.where(last, 0, sep_len[sep])
        tlen = wl + sl
        tend = np.cumsum(tlen, dtype=np.int32)
        tbeg = tend - tlen
        owner = np.repeat(np.arange(tok.size, dtype=np.int32), tlen)
        pos = np.arange(int(tend[-1]), dtype=np.int32) - tbeg[owner]
        word = pos < wl[owner]
        src = np.where(word, wstart[tok][owner] + pos,
                       sep_start[sep][owner] + pos - wl[owner])
        buf = both[src]
        case = rng.uniform(size=tok.size)
        first = tbeg[case < 0.12]                # Capitalised
        shout = (case > 0.99)[owner] & word      # CAPITALS
        letter = buf >= ord("a")
        buf[first] -= 32 * letter[first].astype(np.uint8)
        buf[shout & letter] -= 32
        text = buf.tobytes().decode("ascii")
        ends = tend[row_end[lo:hi] - t0 - 1]
        begs = np.concatenate([[0], ends[:-1]])
        out.extend(text[a:b] for a, b in zip(begs.tolist(), ends.tolist()))
    return out


def _quota(perm: np.ndarray, share: float, skip: int) -> np.ndarray:
    """The rows of a fixed quota: ``round(share x n)`` of them, read off a
    seeded permutation from position ``skip`` on."""
    return perm[skip:skip + int(round(share * perm.size))]


def make(n: int, seed: int, spec: dict, stream: int = 0) -> Table:
    rng = seeded(seed, stream)
    size = int(spec["vocabulary"])
    vocab = vocabulary(size)
    cdf = _zipf_cdf(size, float(spec["zipf_exponent"]))
    perm = rng.permutation(n)
    odd = _quota(perm, float(spec["non_ascii_share"]), 0)
    long_ = _quota(perm, float(spec["long_share"]), odd.size)
    no_title = _quota(perm, float(spec["null_title_share"]),
                      odd.size + long_.size)

    body = spec["text_tokens"]
    n_text = np.clip(np.exp(rng.normal(float(body["log_mean"]),
                                       float(body["log_sd"]), size=n)),
                     int(body["min"]), int(body["max"])).astype(np.int64)
    n_text[long_] = int(body["long"])
    head = spec["title_tokens"]
    n_title = np.clip(rng.geometric(float(head["p"]), size=n),
                      int(head["min"]), int(head["max"])).astype(np.int64)

    def draw(total: int) -> np.ndarray:
        return np.minimum(np.searchsorted(cdf, rng.uniform(size=total)),
                          size - 1)

    text_ids, title_ids = draw(int(n_text.sum())), draw(int(n_title.sum()))
    text = np.array(_render(text_ids, n_text, rng, vocab), dtype=object)
    title = np.array(_render(title_ids, n_title, rng, vocab), dtype=object)
    for j, r in enumerate(odd):
        text[r] = text[r] + NON_ASCII[j % len(NON_ASCII)]
    title[no_title] = None

    lab = spec["label"]
    w = lexicon(size, int(lab["words"]), int(lab["first_rank"]),
                int(lab["last_rank"]))
    row_of = lambda counts: np.repeat(np.arange(n), counts)  # noqa: E731
    score = np.bincount(row_of(n_text), weights=w[text_ids], minlength=n)
    in_title = np.bincount(row_of(n_title), weights=w[title_ids],
                           minlength=n)
    in_title[no_title] = 0.0
    score += float(lab["title_weight"]) * in_title
    # a review of long words reads more favourable: a contrast between the
    # text's length and its token count that no single column carries
    chars = np.frompyfunc(len, 1, 1)(text).astype(np.float64) / n_text
    score += float(lab["word_length_weight"]) * (
        chars - chars.mean()) / chars.std()
    score += rng.normal(0.0, float(lab["noise_sd"]), size=n)
    y = (score > np.median(score)).astype(np.float64)
    return Table(nums={}, cats={"title": title, "text": text}, label=y)
