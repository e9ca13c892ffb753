"""Draft/target speculative decoding compiled as ONE jitted scan.

Plain ``generate()`` (text/generation.py) pays one full target-model
forward per generated token — the dominant cost of autoregressive
serving.  Speculative decoding multiplies tokens per target pass without
changing the output:

  * a small **draft** model proposes ``gamma`` tokens autoregressively
    from its OWN ring cache (``gamma + 1`` cheap single-token forwards —
    the extra one back-fills the last proposal's K/V so the draft cache
    stays committed-prefix-consistent at every acceptance count);
  * the **target** scores all ``gamma + 1`` positions in a SINGLE
    batched verify forward — ``forward_cached`` with a ``gamma + 1``-wide
    ``cache_position`` block write (ring_block_write splits the write at
    the ring boundary);
  * **greedy acceptance** walks the longest prefix where the draft's
    proposal equals the target's own argmax; everything after the first
    disagreement is discarded and the target's token at the disagreement
    point is committed instead — so every emitted token is the target's
    greedy choice over the exact committed prefix and the output is
    bit-identical to plain greedy decode of the target, whatever the
    draft proposes (a random draft only costs speed, never correctness);
  * **rejection rolls both caches back by rewinding cache_position** —
    the ring caches take traced positions, so rollback is a counter
    move, not a copy: stale K/V rows beyond the committed length fall
    outside the validity mask and are overwritten by the next block;
  * batched rows advance in LOCKSTEP (the per-step acceptance is the
    minimum over rows): cache positions stay scalar, so the whole
    propose -> verify -> accept -> rewind loop is one
    ``lax.while_loop`` body inside one jitted program.  At batch 1 this
    is exact speculative decoding; at larger batches the slowest row
    paces the batch (the acceptance-rate histogram shows what that
    costs).

Exactly TWO executables run per ``generate()`` — the joint prefill
(target + draft caches filled in one program) and the scanned
speculative step — ledgered at the Generator's ``generate:<model>`` site
(kinds ``spec_prefill`` / ``spec_decode``), so the zero-per-token- and
zero-steady-state-compile proofs carry over unchanged to the serving
engine's warm-up grid (serving/decode.py registers a draft/target
``DecodeModelSpec`` pair under ``FLAGS_spec_decode``).

Acceptance telemetry: ``spec_proposed_tokens_total`` /
``spec_accepted_tokens_total`` counters and the ``spec_acceptance_ratio``
histogram in the typed MetricsRegistry; traced requests get ``draft`` /
``verify`` child spans under the decode span (durations estimated by the
models' parameter-count ratio — the scan is one device program, so the
host cannot fence the phases; the spans say so via ``estimated=True``).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from ..framework import flags as _flags
from ..framework.enforce import InvalidArgumentError as _InvalidArgument
from ..profiler import tracing as _tracing
from ..profiler.metrics import default_registry as _registry
from .generation import Generator as _Generator
from .generation import _apply_layer, _slice_row, _splice_row

__all__ = ["SpeculativeGenerator"]
SPEC_PROPOSED = _registry().counter(
    "spec_proposed_tokens_total",
    "Draft tokens proposed to the target verifier by speculative "
    "decoding (gamma per speculative step), per generate site.",
    labels=("model",))
SPEC_ACCEPTED = _registry().counter(
    "spec_accepted_tokens_total",
    "Proposed draft tokens the target verifier accepted (the longest "
    "agreeing prefix, minimum over batch rows), per generate site.",
    labels=("model",))
SPEC_ACCEPT_RATIO = _registry().histogram(
    "spec_acceptance_ratio",
    "Per-generate() draft acceptance rate (accepted / proposed): the "
    "knob that decides whether gamma pays for itself.",
    labels=("model",),
    buckets=(0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8, 0.9, 1.0))


def _param_bytes(params):
    return sum(int(v.size) * int(v.dtype.itemsize)
               for v in jax.tree_util.tree_leaves(params))


class SpeculativeGenerator(_Generator):
    """Compiled draft/target speculative decoding for one model pair.

    The Generator contract is preserved exactly — ``prefill(ids, start,
    C)`` returns ``(caches, next-token logits)`` and ``decode(...)``
    returns generated ids ``[B, steps]`` — so the serving decode runtime
    and the bench harness drive it unchanged; only the cache payload is
    now the (target, draft) pair and the decode program is the
    speculative while-loop.  Greedy only: ``beam_size > 1`` raises
    (beam search re-scores whole beams every step — there is no draft
    shortcut to verify against).
    """

    _PREFILL_KIND = "spec_prefill"
    _DECODE_KIND = "spec_decode"

    def __init__(self, layer, draft, site: Optional[str] = None,
                 seq_buckets: Optional[Sequence[int]] = None,
                 max_len: Optional[int] = None,
                 gamma: Optional[int] = None):
        if not hasattr(draft, "forward_cached") \
                or not hasattr(draft, "init_cache"):
            raise _InvalidArgument(
                f"draft {type(draft).__name__} does not implement the "
                "incremental-decoding contract (init_cache + "
                "forward_cached) — see text.models.GPTModel")
        tv = getattr(getattr(layer, "config", None), "vocab_size", None)
        dv = getattr(getattr(draft, "config", None), "vocab_size", None)
        if tv is not None and dv is not None and int(tv) != int(dv):
            raise _InvalidArgument(
                f"draft vocab ({dv}) must match the target vocab ({tv}): "
                "acceptance compares token ids")
        draft.eval()
        self._draft = draft
        g = int(gamma if gamma is not None else _flags.flag("spec_gamma"))
        if g < 1:
            raise _InvalidArgument(f"gamma must be >= 1, got {g}")
        self._gamma = g
        self.last_stats = None
        super().__init__(layer, site=site, seq_buckets=seq_buckets,
                         max_len=max_len)
        # host-side draft/verify attribution ratio for traced spans:
        # both models run ~gamma+1 token-forwards per step, so wall time
        # splits roughly by parameter bytes (annotated estimated=True)
        db = _param_bytes(self._d_params)
        tb = _param_bytes(self._params)
        self._draft_fraction = db / max(db + tb, 1)

    @property
    def gamma(self) -> int:
        return self._gamma

    def _models(self):
        return (self._layer, self._draft)

    @property
    def _d_params(self):
        return self._state[2]

    @property
    def _d_buffers(self):
        return self._state[3]

    def cache_bucket(self, prefill: int, steps: int) -> int:
        """The verify block overshoots the requested steps by up to
        gamma tokens (plus the draft back-fill token), so the cache
        bucket must leave that slack — rollback rewinds the counter, but
        the block WRITE must land inside the ring."""
        return super().cache_bucket(prefill, int(steps) + self._gamma + 1)

    # -- the two pure programs ----------------------------------------------
    def _init_draft_cache_raw(self, B, C):
        ring = self._draft.init_cache(B, C)
        from ..framework.tensor import unwrap
        return [tuple(unwrap(p) for p in c) for c in ring]

    def _build_prefill(self, B, P, C):
        def prefill(tp, tb, dp, db, ids, start):
            t_logits, t_cache = _apply_layer(
                self._layer, tp, tb, ids, self._init_cache_raw(B, C),
                jnp.int32(0), start)
            # the draft consumes the same left-padded prompt so both
            # caches share positions — ONE executable fills both
            _, d_cache = _apply_layer(
                self._draft, dp, db, ids, self._init_draft_cache_raw(B, C),
                jnp.int32(0), start)
            return (t_cache, d_cache), \
                t_logits[:, -1, :].astype(jnp.float32)
        return prefill

    def _build_decode(self, B, C, steps, beam, end):
        if beam != 1:
            raise _InvalidArgument(
                "speculative decoding is greedy-only (beam search "
                "re-scores whole beams — use beam_size=1 or drop the "
                "draft model)")
        gamma = self._gamma
        G1 = gamma + 1
        W = steps + G1                     # emit buffer rows (overshoot)
        target, draft = self._layer, self._draft

        def decode(tp, tb, dp, db, caches, logits0, start, pos0):
            t_cache0, d_cache0 = caches
            cur0 = jnp.argmax(logits0, axis=-1).astype(jnp.int32)
            # [W, B] so the traced-position block write lands on the
            # SUBLANE dim with lanes fully spanned (the exempt pattern)
            buf0 = jnp.zeros((W, B), jnp.int32)
            init = (t_cache0, d_cache0, cur0, jnp.asarray(pos0, jnp.int32),
                    jnp.int32(0), jnp.zeros((B,), bool),
                    jnp.int32(0), jnp.int32(0), jnp.int32(0), buf0)

            def cond(carry):
                return carry[4] < steps

            def body(carry):
                (t_cache, d_cache, cur, t_pos, out_pos, finished,
                 accepted, proposed, nsteps, buf) = carry

                # -- propose: gamma+1 draft forwards; iteration i feeds
                # token i of the block and writes its K/V, so the last
                # proposal's row is back-filled and the draft cache is a
                # valid committed prefix at ANY acceptance count
                def dstep(dc, _):
                    cache, tok, p = dc
                    lg, cache = _apply_layer(draft, dp, db, tok[:, None],
                                             cache, p, start)
                    nxt = jnp.argmax(lg[:, 0], axis=-1).astype(jnp.int32)
                    return (cache, nxt, p + 1), tok

                (d_cache, _, _), fed = lax.scan(
                    dstep, (d_cache, cur, t_pos), None, length=G1)
                v_in = jnp.transpose(fed)          # [B, G1]: cur, d1..dγ

                # -- verify: ONE gamma+1-wide target forward; the block
                # write lands at t_pos (rollback later = rewind t_pos)
                v_logits, t_cache = _apply_layer(target, tp, tb, v_in,
                                                 t_cache, t_pos, start)
                g = jnp.argmax(v_logits, axis=-1).astype(jnp.int32)

                # -- accept: longest prefix where the draft agreed with
                # the target's own argmax; lockstep = min over rows
                # (finished rows report gamma so they never pace)
                match = (v_in[:, 1:] == g[:, :-1]).astype(jnp.int32)
                n_row = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
                n = jnp.min(jnp.where(finished, gamma, n_row)) \
                    .astype(jnp.int32)
                ncommit = n + 1                    # block tokens emitted
                cur_next = jnp.take_along_axis(
                    g, jnp.broadcast_to(n, (B,))[:, None], axis=1)[:, 0]

                # -- emit: the committed block is v_in[:, :ncommit]; eos
                # freezes rows exactly like the greedy scan (every
                # position after an eos — or on an already-finished
                # row — reads eos)
                is_end = (v_in == jnp.int32(end))
                before = (jnp.cumsum(is_end.astype(jnp.int32), axis=1)
                          - is_end.astype(jnp.int32))
                e = jnp.where(finished[:, None] | (before > 0),
                              jnp.int32(end), v_in)
                col = jnp.arange(G1, dtype=jnp.int32)
                finished2 = finished | jnp.any(
                    (e == jnp.int32(end)) & (col[None, :] < ncommit),
                    axis=1)
                cur_next = jnp.where(finished2, jnp.int32(end), cur_next)
                buf = lax.dynamic_update_slice(
                    buf, jnp.transpose(e), (out_pos, jnp.int32(0)))

                # -- rewind: both caches roll back to the committed
                # length by moving the position counter; the rejected
                # rows are dead weight outside the validity window
                return (t_cache, d_cache, cur_next, t_pos + ncommit,
                        out_pos + ncommit, finished2, accepted + n,
                        proposed + jnp.int32(gamma), nsteps + 1, buf)

            out = lax.while_loop(cond, body, init)
            toks = jnp.transpose(out[9])[:, :steps]
            return toks, out[6], out[7], out[8]

        return decode

    # -- slot-loop programs (serving/slots.py) -------------------------------
    def _build_step(self, S, C, end):
        """ONE speculative step over ``S`` slot rows — the while-loop
        body hoisted so the host owns the loop.  Two slot-specific
        inputs: ``active`` keeps empty/mid-prefill rows from pacing the
        lockstep acceptance (they report gamma, like finished rows);
        ``max_commit`` clamps the commit
        count so the variable stride lands EXACTLY on the host's next
        chunk/activation boundary — committing fewer tokens than the
        target accepted is always exact (the next token is the target's
        argmax at the clamped position), it only costs speed."""
        gamma = self._gamma
        G1 = gamma + 1
        target, draft = self._layer, self._draft

        def step(tp, tb, dp, db, caches, cur, start, finished, active,
                 pos, max_commit):
            t_cache, d_cache = caches
            cur_safe = jnp.where(active, cur, jnp.int32(0))

            def dstep(dc, _):
                cache, tok, p = dc
                lg, cache = _apply_layer(draft, dp, db, tok[:, None],
                                         cache, p, start)
                nxt = jnp.argmax(lg[:, 0], axis=-1).astype(jnp.int32)
                return (cache, nxt, p + 1), tok

            (d_new, _, _), fed = lax.scan(
                dstep, (d_cache, cur_safe, pos), None, length=G1)
            v_in = jnp.transpose(fed)              # [S, G1]
            v_logits, t_new = _apply_layer(target, tp, tb, v_in, t_cache,
                                           pos, start)
            g = jnp.argmax(v_logits, axis=-1).astype(jnp.int32)
            match = (v_in[:, 1:] == g[:, :-1]).astype(jnp.int32)
            n_row = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
            n = jnp.min(jnp.where(finished | ~active, gamma, n_row)) \
                .astype(jnp.int32)
            ncommit = jnp.minimum(n + 1, max_commit)
            cur_next = jnp.take_along_axis(
                g, jnp.broadcast_to(ncommit - 1, (S,))[:, None],
                axis=1)[:, 0]
            is_end = (v_in == jnp.int32(end))
            before = (jnp.cumsum(is_end.astype(jnp.int32), axis=1)
                      - is_end.astype(jnp.int32))
            e = jnp.where(finished[:, None] | (before > 0),
                          jnp.int32(end), v_in)
            col = jnp.arange(G1, dtype=jnp.int32)
            finished2 = finished | jnp.any(
                (e == jnp.int32(end)) & (col[None, :] < ncommit), axis=1)
            cur_next = jnp.where(finished2, jnp.int32(end), cur_next)
            # no per-row cache blend: both caches are donated and a
            # blend would force a full-plane protective copy per step —
            # inactive rows' garbage block [pos, pos+G1) is dead by the
            # host chunk schedule (slots._dispatch_chunks) and by the
            # next active dispatch rewriting [pos', pos'+G1) before any
            # commit exposes it
            return (t_new, d_new), cur_next, finished2, e, ncommit, n

        return step

    def chunk_row(self):
        """The joint chunk below cuts its row out of both caches."""
        return "sliced"

    def _build_chunk(self, S, T, C):
        """One JOINT prefill chunk: target and draft both consume the
        joining row's ``T`` prompt tokens at the block position, so the
        two caches stay position-aligned exactly like the joint prefill
        executable.  Single-row like the plain chunk — both forwards
        run at batch 1 over the row's sliced planes.  Returns the
        target's last-column logits."""
        target, draft = self._layer, self._draft

        def chunk(tp, tb, dp, db, caches, ids, start, rowidx, pos):
            t_cache, d_cache = caches
            t_sub = _slice_row(t_cache, rowidx)
            d_sub = _slice_row(d_cache, rowidx)
            t_logits, t_new = _apply_layer(target, tp, tb, ids, t_sub,
                                           pos, start)
            _, d_new = _apply_layer(draft, dp, db, ids, d_sub, pos,
                                    start)
            return (_splice_row(t_cache, t_new, rowidx),
                    _splice_row(d_cache, d_new, rowidx)), \
                t_logits[0, -1, :].astype(jnp.float32)

        return chunk

    def _step_program(self, S, C, eos_token_id=None):
        """The single speculative step over ``S`` slots (ledger kind
        ``spec_step``)."""
        end = -1 if eos_token_id is None else int(eos_token_id)
        return (self._key("step2", S, None, C, 1, 1, end), "spec_step",
                self._build_step(S, C, end), self.step_avals(S, C),
                {"slots": S, "cache": C, "eos": end, "gamma": self._gamma},
                (4,))

    def _chunk_program(self, S, T, C):
        """The joint prefill chunk over ``S`` slots (ledger kind
        ``spec_chunk``)."""
        return (self._key("chunk2", S, T, C, None, None), "spec_chunk",
                self._build_chunk(S, T, C), self.chunk_avals(S, T, C),
                {"slots": S, "chunk": T, "cache": C, "gamma": self._gamma},
                (4,))

    def step_avals(self, S, C):
        """Non-state avals of the speculative slot step (cache pair,
        cur, start, finished, active, pos, max_commit)."""
        caches = (self._slot_cache_avals(S, C),
                  self._slot_draft_cache_avals(S, C))
        return (caches,
                jax.ShapeDtypeStruct((S,), jnp.int32),
                jax.ShapeDtypeStruct((S,), jnp.int32),
                jax.ShapeDtypeStruct((S,), jnp.bool_),
                jax.ShapeDtypeStruct((S,), jnp.bool_),
                jax.ShapeDtypeStruct((), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32))

    def chunk_avals(self, S, T, C):
        """Non-state avals of the single-row joint prefill-chunk
        program (cache pair, ids [1, T], start [1], row index, block
        position)."""
        caches = (self._slot_cache_avals(S, C),
                  self._slot_draft_cache_avals(S, C))
        return (caches,
                jax.ShapeDtypeStruct((1, T), jnp.int32),
                jax.ShapeDtypeStruct((1,), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32))

    def _slot_draft_cache_avals(self, S, C):
        raw = jax.eval_shape(lambda: self._init_draft_cache_raw(S, C))
        return [tuple(jax.ShapeDtypeStruct(p.shape, p.dtype) for p in c)
                for c in raw]

    def slot_cache_avals_all(self, S, C):
        """The speculative step donates the (target, draft) cache PAIR —
        the KV data movers must pull/push both, or a restored session
        would decode against a stale draft cache and break acceptance."""
        return (self._slot_cache_avals(S, C),
                self._slot_draft_cache_avals(S, C))

    def init_slot_cache(self, S, C):
        """Zero (target, draft) cache pair for a fresh slot session."""
        t = super().init_slot_cache(S, C)
        raw = jax.eval_shape(lambda: self._init_draft_cache_raw(S, C))
        d = [tuple(jnp.zeros(tuple(p.shape), p.dtype) for p in c)
             for c in raw]
        return (t, d)

    # -- AOT compile + ledger ------------------------------------------------
    def _key(self, phase, B, P, C, steps, beam, end=None):
        return super()._key(phase, B, P, C, steps, beam, end) \
            + (("arg:gamma", self._gamma),)

    def _program_identity(self):
        # the joint program bakes the draft architecture too (its params
        # ride _state_avals already; the class/config pin the code path)
        d_cfg = getattr(self._draft, "config", None)
        d_cfg_r = repr(sorted(vars(d_cfg).items())) \
            if d_cfg is not None and hasattr(d_cfg, "__dict__") \
            else repr(d_cfg)
        return super()._program_identity() + (
            "draft", type(self._draft).__name__, d_cfg_r, self._gamma)

    def prefill_exec(self, B, P, C):
        key = self._key("prefill", B, P, C, None, None)
        fn = self._build_prefill(B, P, C)
        avals = (jax.ShapeDtypeStruct((B, P), jnp.int32),
                 jax.ShapeDtypeStruct((B,), jnp.int32))
        return self._compile(key, self._PREFILL_KIND, fn, avals,
                             {"batch": B, "prompt": P, "cache": C,
                              "gamma": self._gamma})

    def decode_exec(self, B, C, steps, beam=1, eos_token_id=None):
        end = -1 if eos_token_id is None else int(eos_token_id)
        key = self._key("decode", B, None, C, steps, beam, end)
        fn = self._build_decode(B, C, int(steps), int(beam), end)
        avals_of = lambda raw: [tuple(jax.ShapeDtypeStruct(p.shape, p.dtype)
                                      for p in c) for c in raw]
        t_avals = avals_of(jax.eval_shape(
            lambda: self._init_cache_raw(B, C)))
        d_avals = avals_of(jax.eval_shape(
            lambda: self._init_draft_cache_raw(B, C)))
        vocab = self._vocab_size()
        avals = ((t_avals, d_avals),
                 jax.ShapeDtypeStruct((B, vocab), jnp.float32),
                 jax.ShapeDtypeStruct((B,), jnp.int32),
                 jax.ShapeDtypeStruct((), jnp.int32))
        return self._compile(key, self._DECODE_KIND, fn, avals,
                             {"batch": B, "cache": C, "steps": int(steps),
                              "beam": int(beam), "gamma": self._gamma})

    # -- execution ----------------------------------------------------------
    def decode(self, cache, logits0, start, pos0, steps, beam_size=1,
               eos_token_id=None):
        """Run (compiling if new) the speculative while-loop from a
        prefill result; returns tokens [B, steps] — bit-identical to the
        plain greedy decode of the target.  Publishes acceptance
        telemetry (counters + histogram + ``last_stats``)."""
        B = logits0.shape[0]
        C = cache[0][0][0].shape[2]
        ex = self.decode_exec(B, int(C), int(steps), int(beam_size),
                              eos_token_id)
        toks, accepted, proposed, nsteps = ex(
            *self._state_args(), cache,
            jnp.asarray(logits0, jnp.float32),
            jnp.asarray(start, jnp.int32), jnp.int32(pos0))
        a, p, s = int(accepted), int(proposed), int(nsteps)
        rate = a / p if p else 0.0
        SPEC_PROPOSED.labels(model=self._site).inc(p)
        SPEC_ACCEPTED.labels(model=self._site).inc(a)
        SPEC_ACCEPT_RATIO.labels(model=self._site).observe(rate)
        self.last_stats = {
            "gamma": self._gamma, "accepted": a, "proposed": p,
            "spec_steps": s, "acceptance_rate": round(rate, 4),
            "draft_fraction": round(self._draft_fraction, 4),
        }
        return toks

    def _annotate_decode_span(self, d, t1, t2, steps):
        """The speculative step is one device program: split the fenced
        decode window into estimated ``draft``/``verify`` child spans by
        the models' parameter-byte ratio and attach the measured
        acceptance stats."""
        st = self.last_stats or {}
        tm = t1 + (t2 - t1) * self._draft_fraction
        _tracing.child(d, "draft", t1, tm, estimated=True,
                       gamma=self._gamma, proposed=st.get("proposed"),
                       spec_steps=st.get("spec_steps"))
        _tracing.child(d, "verify", tm, t2, estimated=True,
                       accepted=st.get("accepted"),
                       acceptance_rate=st.get("acceptance_rate"))
        d.set_attr(gamma=self._gamma,
                   acceptance_rate=st.get("acceptance_rate"),
                   spec_steps=st.get("spec_steps"))
