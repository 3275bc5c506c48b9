"""Draft proposers for speculative decoding (``docs/serving.md``).

Speculative decoding splits one decode iteration into *draft* (guess
the next few tokens cheaply) and *verify* (score every guess in one
multi-token engine step — ``DecodeEngine.verify``, built on
``ops.chunk_cached_attention``).  Greedy acceptance keeps the output
bit-identical to plain one-token decode by construction: the accepted
tokens are exactly the drafts that MATCH the model's own argmax at
their position, followed by the model's own next token — so a wrong
draft costs one wasted verify column, never a wrong output token.

This module is the draft half.  The default proposer is zero-weight
**prompt-lookup / n-gram drafting** (Saxena's prompt-lookup decoding;
the LLMA observation): generation frequently copies spans that already
occurred in the request's own context — few-shot templates, quoted
retrieval passages, code identifiers, and the self-generated suffix of
any repetitive completion — so the best free guess for "what follows
the current suffix" is "what followed it last time it appeared".  No
extra weights, no extra compiled programs, no second model to keep in
HBM.  The server's loop drafts for every running request every step,
so :class:`NgramDraft` keeps an index of each request's history on the
request (``Request.draft_index``): a call indexes only the tokens
appended since the last one and answers each n-gram lookup from a
dict, where a scan of the window would cost O(window * max_ngram).

:class:`DraftSource` is the pluggable interface: a small-model drafter
(the classic Leviathan et al. setup) is a subclass whose
:meth:`~DraftSource.propose` greedily decodes ``k`` tokens from its
own cheap model — the verify/acceptance machinery upstream is
identical and stays bit-exact regardless of where drafts come from,
because acceptance only ever compares drafts against the target
model's own argmax.

Stochastic requests (``docs/serving.md``, "Stochastic sampling")
use the SAME drafts and the same acceptance comparison, but against
each verify column's counter-keyed SAMPLE instead of its argmax —
rejection sampling with the proposer's tokens as a delta ``q``
(accept prob ``p(draft)``, residual resample on first rejection),
realized via the Gumbel-max coupling so the emitted stream is
byte-identical with speculation on or off.  Draft determinism (the
contract below) matters doubly there: the chaos soak replays
per-step accounting, and drafts must be pure functions of history.
"""

from __future__ import annotations

from typing import Any, List, Optional, Sequence, Tuple

__all__ = ["DraftSource", "NgramDraft"]


def _span(prompt: Sequence[int], generated: Sequence[int], a: int,
          b: int) -> List[int]:
    """``(prompt + generated)[a:b]``, without joining the two."""
    n = len(prompt)
    if a >= n:
        return list(generated[a - n:b - n])
    if b <= n:
        return list(prompt[a:b])
    return list(prompt[a:]) + list(generated[:b - n])


class DraftSource:
    """Interface for draft proposers.

    ``propose(tokens, k)`` receives the request's full token history
    (prompt + everything generated so far, INCLUDING the pending token
    whose K/V the next engine step will write) and returns up to ``k``
    guesses for the tokens that follow.  Returning ``[]`` means "no
    guess" — the request decodes one token normally that iteration.

    Contract notes for implementers:

    - drafts are *hints*, never outputs: a wrong draft is rejected by
      verify and costs only wasted compute, so proposers may guess
      aggressively;
    - ``propose`` runs on the host inside the serve loop, once per
      decoding request per iteration — it must be cheap relative to a
      device step;
    - determinism matters: a request replayed with the same history
      must get the same drafts, or OOM-retry and the chaos soak's
      bit-exact replay would wobble (outputs stay bit-exact either
      way, but per-step accounting would not reproduce).
    """

    def propose(self, tokens: Sequence[int], k: int) -> List[int]:
        raise NotImplementedError

    def propose_indexed(self, prompt: Sequence[int],
                        generated: Sequence[int], k: int,
                        index: Any = None) -> Tuple[List[int], Any]:
        """``propose(prompt + generated, k)`` as the server's loop asks
        it, once per running request per step: returns the drafts and
        an ``index`` the caller keeps with the request and hands back
        next time.  The index may only cache the history — the drafts
        stay a function of ``prompt + generated`` — and a new object
        means it was built from scratch.  The default joins the
        history, calls :meth:`propose` and keeps no index."""
        return self.propose(prompt + generated, k), None

    def reset(self) -> None:
        """Drop any cross-request state (stateless by default)."""


class NgramDraft(DraftSource):
    """Zero-weight prompt-lookup drafts from the request's own history.

    For ``n`` from ``max_ngram`` down to ``min_ngram``: take the last
    ``n`` tokens of the history, find the most recent EARLIER
    occurrence of that n-gram, and propose the ``k`` tokens that
    followed it.  Longer n-grams are tried first (a longer matched
    context is a stronger predictor); the most recent occurrence wins
    because generation drifts — what followed the suffix lately beats
    what followed it long ago.

    ``history_window`` bounds the lookup (the last N tokens of
    history; ``None`` searches all of it).  :meth:`propose_indexed`
    keeps a :class:`_NgramIndex` of the request's history, so a call
    costs O(new tokens * max_ngram) to index and O(k * max_ngram) to
    draft, and the index holds at most about twice the window;
    :meth:`propose` builds a throwaway index, O(window * max_ngram).
    """

    def __init__(self, max_ngram: int = 3, min_ngram: int = 1,
                 history_window: Optional[int] = 512):
        if max_ngram < min_ngram or min_ngram < 1:
            raise ValueError(
                f"need max_ngram >= min_ngram >= 1; got "
                f"max_ngram={max_ngram} min_ngram={min_ngram}")
        if history_window is not None and history_window < 2:
            raise ValueError(
                f"history_window must be >= 2, got {history_window}")
        self.max_ngram = int(max_ngram)
        self.min_ngram = int(min_ngram)
        self.history_window = history_window

    def propose(self, tokens: Sequence[int], k: int) -> List[int]:
        return self.propose_indexed(tokens, (), k)[0]

    def propose_indexed(self, prompt: Sequence[int],
                        generated: Sequence[int], k: int,
                        index: Any = None) -> Tuple[List[int], Any]:
        end = len(prompt) + len(generated)
        w = self.history_window
        start = 0 if w is None else max(0, end - w)
        # reuse the index while the history extends what it indexed and
        # its span stays within twice the window; else index the window
        # afresh (a request's first call, a foreign history, compaction)
        if (isinstance(index, _NgramIndex) and index.owner is self
                and index.extended_by(prompt, generated, end)
                and (w is None or end - index.base <= 2 * w)):
            index.extend(_span(prompt, generated, index.end, end))
        else:
            index = _NgramIndex(self, start,
                                _span(prompt, generated, start, end))
        return index.draft(start, k), index


class _NgramIndex:
    """What :class:`NgramDraft` keeps of one request's history: the
    tokens from ``base`` on, and for each n a dict from n-gram to the
    latest start ``i`` whose n-gram has a follower in the history
    (``i + n < end``).  The most recent occurrence in a window that
    begins at ``start`` is the dict's entry if it is ``>= start``, and
    there is none otherwise."""

    __slots__ = ("owner", "base", "toks", "maps")

    def __init__(self, owner: NgramDraft, base: int, toks: List[int]):
        self.owner, self.base, self.toks = owner, base, []
        self.maps: List[Optional[dict]] = [
            {} if n >= owner.min_ngram else None
            for n in range(owner.max_ngram + 1)]
        self.extend(toks)

    @property
    def end(self) -> int:
        return self.base + len(self.toks)

    def extended_by(self, prompt, generated, end: int) -> bool:
        """Whether the history is at least as long as what was indexed
        and ends it with the same last tokens (the newest n-gram and its
        follower)."""
        toks, e = self.toks, self.end
        m = min(len(toks), self.owner.max_ngram + 1)
        return e <= end and (_span(prompt, generated, e - m, e)
                             == toks[len(toks) - m:])

    def extend(self, new: List[int]) -> None:
        toks, base = self.toks, self.base
        old = len(toks)
        toks.extend(new)
        for n in range(self.owner.min_ngram, self.owner.max_ngram + 1):
            # the starts whose follower is one of the new tokens, in
            # order, so that a later start overwrites an earlier one
            a, b = max(0, old - n), len(toks) - n
            if b > a:
                grams = (toks[a:b] if n == 1 else
                         zip(*[toks[a + j:b + j] for j in range(n)]))
                self.maps[n].update(zip(grams, range(base + a, base + b)))

    def draft(self, start: int, k: int) -> List[int]:
        """Up to ``k`` drafts from the window ``[start, end)``: for n
        from ``max_ngram`` down, the token that followed the most recent
        earlier occurrence of the last n tokens.  Each guess joins the
        working history — re-matching the EXTENDED suffix extrapolates a
        periodic tail (the common repetitive-completion shape) to a full
        k-token draft instead of stopping at the history's edge — but not
        the index: the at most j starts whose n-gram or follower is one
        of the j guesses so far are the most recent, and are scanned
        first."""
        lo, hi = self.owner.min_ngram, self.owner.max_ngram
        toks, base, maps, end = self.toks, self.base, self.maps, self.end
        t = min(len(toks), hi)
        work = toks[len(toks) - t:]        # the last tokens, then guesses
        off = end - t                      # the position of work[0]
        out: List[int] = []
        for m in range(end, end + max(0, k)):   # m: working length
            nxt = None
            for n in range(min(hi, m - start - 1), lo - 1, -1):
                suffix = work[len(work) - n:]
                for i in range(m - n - 1, max(start, end - n) - 1, -1):
                    if work[i - off:i - off + n] == suffix:
                        nxt = work[i - off + n]
                        break
                else:
                    i = maps[n].get(suffix[0] if n == 1 else tuple(suffix))
                    if i is not None and i >= start:
                        nxt = toks[i - base + n]
                if nxt is not None:
                    break
            if nxt is None:
                break
            out.append(int(nxt))
            work.append(nxt)
        return out
