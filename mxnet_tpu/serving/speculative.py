"""Speculative decoding over the paged pool (docs/SERVING.md §Prefix
cache & speculative decoding).

Decode latency is dispatch-bound, not FLOP-bound: the megastep work
amortized the per-token host gap, and speculation amortizes the
per-token DISPATCH. A small draft model (the target's first k blocks —
``models/transformer.py draft_config``; weight names are positional so
the TARGET checkpoint feeds it unchanged) proposes γ tokens inside its
own decode megastep, then the target scores all γ+1 candidate positions
in ONE rectangular chunk dispatch (``PagedKVDecoder.verify_chunk``).
Greedy acceptance keeps the longest prefix where the draft's token
equals the target's argmax, emits the target's own token at the first
disagreement, and ``rollback`` releases the rejected tail's pages —
a refcount decrement, no copy, no device work. Because every emitted
token is the target's argmax given the exact same visible KV, the
output stream is TOKEN-IDENTICAL to non-speculative greedy decode:
speculation only changes how many dispatches it takes to produce it —
the ci parity gate pins exactly that.

Round protocol (target and draft both at position p, next token ``cur``):

1. draft megastep(k=γ) from ``cur`` → proposals props[0..γ-1]
   (draft writes positions p..p+γ-1, i.e. cur and props[:-1])
2. target ``verify_chunk([cur] + props)`` → γ+1 logits rows in one
   dispatch (target writes positions p..p+γ)
3. accept props[j] while props[j] == argmax(row j); at the first miss
   emit the target's argmax instead; n_acc accepted → n_acc+1 emitted
4. rollback BOTH decoders to p + n_acc + 1 (whole rejected pages are
   released; a partial boundary page just masks its stale tail)
5. fully-accepted rounds advance the draft one extra plain step so it
   re-synchronizes (it never wrote props[γ-1])
"""
from __future__ import annotations

import os

import numpy as np

from ..base import MXNetError
from .. import telemetry as _tm
from .kv_decode import PagedKVDecoder

__all__ = ["SpeculativeDecoder", "spec_decode_enabled", "spec_gamma"]


def spec_decode_enabled():
    """``MXNET_SPEC_DECODE`` truthy -> serving loops that support it use
    draft-verify speculative decoding."""
    return os.environ.get("MXNET_SPEC_DECODE", "").strip().lower() \
        in ("1", "on", "true", "yes")


def spec_gamma(default=4):
    """Draft tokens proposed per round (``MXNET_SPEC_GAMMA``). Junk or
    non-positive values fall back to ``default``."""
    raw = os.environ.get("MXNET_SPEC_GAMMA", "").strip()
    if not raw:
        return int(default)
    try:
        g = int(raw)
    except ValueError:
        return int(default)
    return g if g >= 1 else int(default)


class SpeculativeDecoder:
    """Draft-verify speculative greedy decode over two paged decoders.

    ``target`` and ``draft`` are ``PagedKVDecoder``s sharing the vocab
    (normally the draft is the same checkpoint at fewer layers — see
    ``build``). Admission runs on both; each decode round costs one
    draft megastep + one target verify chunk instead of γ+1 target
    dispatches, recovering latency whenever the draft's agreement rate
    beats the draft's relative cost."""

    def __init__(self, target: PagedKVDecoder, draft: PagedKVDecoder,
                 gamma=None):
        if target.vocab_size != draft.vocab_size:
            raise MXNetError(
                "speculative: target vocab %d != draft vocab %d"
                % (target.vocab_size, draft.vocab_size))
        self.target = target
        self.draft = draft
        self.gamma = int(gamma) if gamma is not None else spec_gamma()
        if self.gamma < 1:
            raise MXNetError("speculative: gamma must be >= 1, got %d"
                             % self.gamma)
        self._pairs = {}  # target seq_id -> draft seq_id

    @classmethod
    def build(cls, arg_params, vocab_size, num_layers=2, draft_layers=1,
              gamma=None, model_key=None, **kw):
        """Target + draft from ONE checkpoint: the draft is the same
        config truncated to its first ``draft_layers`` blocks
        (positional weight names; extra checkpoint entries are ignored
        at bind, as in the predict API's allow_extra_params)."""
        from ..models.transformer import draft_config

        cfg = dict(vocab_size=vocab_size, num_layers=num_layers, **kw)
        dcfg = draft_config(cfg, draft_layers)
        target = PagedKVDecoder(arg_params, model_key=model_key, **cfg)
        draft = PagedKVDecoder(
            arg_params,
            model_key=(model_key or "transformer_paged_global_decode")
            + "-draft%d" % draft_layers, **dcfg)
        return cls(target, draft, gamma=gamma)

    # ------------------------------------------------------------ lifecycle
    def warmup(self):
        """Compile every program a decode round replays — the target's
        decode executable + (γ+1)-chunk verify, the draft's decode
        executable + γ-megastep — so the steady state is all cache
        hits."""
        from .kv_decode import _megastep_for, _sampler_from

        self.target.warmup()
        self.draft.warmup()
        self.target._chunk_for(self.gamma + 1)
        _megastep_for(self.draft, self.gamma,
                      _sampler_from(None, None, None))
        return self

    def admit(self, prompt):
        """Admit into BOTH decoders. Returns ``(seq_id, logits)`` in the
        target's namespace; the paired draft sequence is internal."""
        seq_id, logits = self.target.admit(prompt)
        try:
            d_id, _ = self.draft.admit(prompt)
        except BaseException:
            self.target.retire(seq_id)
            raise
        self._pairs[seq_id] = d_id
        return seq_id, logits

    def retire(self, seq_id):
        d_id = self._pairs.pop(seq_id, None)
        self.target.retire(seq_id)
        if d_id is not None:
            self.draft.retire(d_id)

    def stats(self):
        return {"gamma": self.gamma,
                "target": self.target.stats(),
                "draft": self.draft.stats()}

    # --------------------------------------------------------------- decode
    def _room(self, seq_id, d_id):
        """Largest γ a round can use at the current position: the target
        writes γ+1 positions, the draft γ+1 (γ in the megastep plus at
        most one catch-up step) — both bounded by their position tables
        and per-lane slot quotas."""
        p = self.target.position(seq_id)
        lim = min(self.target.pos_len, self.target.max_len,
                  self.draft.pos_len, self.draft.max_len)
        return min(self.gamma, lim - p - 1)

    def greedy(self, prompt, n_tokens):
        """Greedy-decode ``n_tokens`` continuation tokens for one
        prompt, speculatively. Returns a (n_tokens,) int64 array that is
        token-identical to ``PagedKVDecoder.greedy`` on the target
        alone."""
        seq_id, logits = self.admit(prompt)
        d_id = self._pairs[seq_id]
        try:
            out = np.zeros((n_tokens,), np.int64)
            if n_tokens == 0:
                return out
            cur = int(np.argmax(logits))
            out[0] = cur
            t = 1
            g = self.gamma
            while t < n_tokens:
                if self._room(seq_id, d_id) < g:
                    # not enough table room for a FULL γ round — a
                    # shorter round would compile fresh (γ'+1)-chunk and
                    # γ'-megastep programs post-warmup, so the tail runs
                    # plain warm single steps instead
                    fed = cur
                    # graphlint: waive GL702 -- position-table tail; single-step program is already warm
                    lg = self.target.step({seq_id: fed})
                    # graphlint: waive GL703 -- the row answers with the program's greedy_token; no logits cross
                    cur = int(np.argmax(lg[seq_id]))
                    # keep the draft aligned in case room returns later
                    # graphlint: waive GL702 -- draft shadow step, same warm program
                    self.draft.step({d_id: fed})
                    out[t] = cur
                    t += 1
                    continue
                p = self.target.position(seq_id)
                # graphlint: waive GL702 -- the γ-token round IS the amortization: one scan dispatch proposes γ tokens
                props = self.draft.step_megastep({d_id: cur}, k=g)[d_id]
                rows = self.target.verify_chunk(
                    seq_id, np.concatenate(([cur], props)))
                # graphlint: waive GL703 -- γ+1 argmaxes on one already-pulled verify block, not per-token pulls
                ids = np.argmax(rows, axis=1).astype(np.int64)
                n_acc = 0
                while n_acc < g and props[n_acc] == ids[n_acc]:
                    n_acc += 1
                emitted = list(props[:n_acc]) + [int(ids[n_acc])] \
                    if n_acc < g else list(props) + [int(ids[g])]
                if n_acc < g:
                    self.target.rollback(seq_id, p + n_acc + 1)
                    self.draft.rollback(d_id, p + n_acc + 1)
                else:
                    # full accept: the draft never wrote props[-1] —
                    # one catch-up step re-synchronizes the pair
                    # graphlint: waive GL702 -- ≤1 catch-up step per γ-token round
                    self.draft.step({d_id: int(props[-1])})
                if _tm.enabled():
                    _tm.counter("spec.proposed_tokens").inc(int(g))
                    _tm.counter("spec.accepted_tokens").inc(n_acc)
                    _tm.counter("spec.rounds").inc()
                for tok in emitted:
                    if t >= n_tokens:
                        break
                    out[t] = tok
                    t += 1
                cur = int(emitted[-1])
            return out
        finally:
            self.retire(seq_id)
