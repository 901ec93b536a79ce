"""Heterogeneous speculative decoding across EMT technology corners.

The EMT-native twist on speculative decoding (ROADMAP item 3): the *draft*
and *target* are the **same weights** on two different device placements of
one heterogeneous fabric.  A cheap deterministic `sram_digital` placement
(amplitude-0 reads — quantization still applies, so it is a faithful digital
execution of the same network) proposes ``k`` tokens per slot; the expensive
analog placement (PCM/RRAM) then scores all ``k`` proposals in **one**
mixed-step call — ``lm.chunk_step`` with ``all_lanes=True`` is exactly the
verify primitive, since chunked prefill already writes rows at exact
positions and returns per-lane logits.

Why this saves analog energy at all: the per-lane MAC/ADC energy of a
(k+1)-lane verify chunk is the same as k+1 single-lane decode steps — the
win comes from the **per-step static macro-activation cost**
(:meth:`~repro.core.device.DeviceModel.static_energy`, the array-to-system
gap of measured PCM silicon): one verify step biases each crossbar tile
*once* for k+1 token positions, where plain decode pays the static tax per
token.  Acceptance rate then decides whether the (k - L) rejected lanes'
dynamic energy eats the static savings — the bench sweeps this
(benchmarks/bench_speculative.py).

Acceptance rule (greedy/temperature-0 only): lane ``j`` of the verify chunk
``[last_token, d_1 .. d_k]`` yields the target's greedy continuation after
``.. d_j``; the longest prefix of drafts matching those continuations is
accepted and the first mismatching lane's greedy token is committed as the
correction (or, when all k match, lane k's token rides along as a bonus) —
so every committed token **is** the target's greedy token given its prefix,
and generation is token-identical to plain greedy decode on the target
placement (deterministic-noise property, tests/test_speculative.py).

Energy accounting: both placements bill into the **same** engine ledger
(total / idle / per-corner — the draft corner label just appears alongside
the analog ones), so the conservation invariant *per-request + idle ==
total* keeps holding across both engines' corners, for partials and
cancellations too.  The draft-side subset is additionally tracked per
request (``draft_energy_pj``) and per engine
(``draft_total_energy_pj``/``draft_idle_energy_pj``), giving the
draft/verify split without a second invariant.
"""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.emt_linear import with_weight_stats
from repro.core.placement import emt_for_corner
from repro.models import lm
from repro.serve.engine import (GenResult, ServingEngine, make_chunk_step,
                                make_serve_decode_step, make_verify_step)


class SpeculativeEngine(ServingEngine):
    """ServingEngine whose decode rounds draft ``spec_k`` tokens on a cheap
    digital placement and verify them in one all-lane chunk step on the
    analog target placement.

    The draft runs the *same parameters* (``draft_params`` defaults to the
    target's) against a contiguous shadow KV cache that mirrors every write
    the target makes: prefill lanes are mirrored lane-for-lane, committed
    tokens re-enter through the next round's draft decodes.

    Every analog round is the **same** (k+1)-lane verify chunk — a slot
    still streaming its prompt occupies its lanes with the next <= k+1
    prompt tokens (its last lane's argmax is the first generated token when
    the prompt completes) while its co-tenants keep speculating.  The spec
    engine therefore *never* runs the wide ``prefill_chunk`` mixed step:
    chunk energy is billed for all B x C lanes (padding included, the lanes
    physically flow through the crossbars), so folding admissions into the
    rounds that run anyway makes their marginal analog cost ~zero, where a
    fallback to the wide chunk paid B x prefill_chunk lanes per admission —
    ruinous under staggered retirements, which fragment a wave of arrivals
    into several admission rounds.

    Greedy only (``temperature == 0`` is enforced at validate()); chunked
    prefill is required (the verify step *is* a chunk step) and the prefix
    cache is not supported yet (the draft cache cannot share blocks, so a
    cache-skipped prefix would leave the draft blind).
    """

    def __init__(self, cfg, params, batch_size: int, max_len: int,
                 mesh=None, rules=None, draft_placement="sram_digital",
                 spec_k: int = 4, draft_params=None, **kw):
        super().__init__(cfg, params, batch_size, max_len, mesh=mesh,
                         rules=rules, **kw)
        if self.n_shards > 1:
            raise ValueError("speculative decoding is not sharded yet: the "
                             "draft shadow cache and verify step are single-"
                             "device (n_shards must be 1)")
        if not self.chunked:
            raise ValueError("speculative decoding requires chunked prefill "
                             "(the verify primitive is the chunk step)")
        if self.prefix_cache:
            raise ValueError("prefix_cache is not supported with speculative "
                             "decoding (the draft shadow cache cannot share "
                             "prefix blocks)")
        if self.cfg.sliding_window and "local" in self.cfg.blocks():
            # a rejected draft's write into a sliding-window *ring* buffer
            # wraps onto (and destroys) the oldest still-visible history —
            # position-indexed global K/V just gets harmlessly overwritten by
            # the next round's chunk, but a clobbered ring slot is never
            # rewritten.  Same restriction (and same reason) as prefix_cache.
            raise ValueError("speculative decoding requires an all-global "
                             "attention stack (rejected drafts would clobber "
                             "sliding-window ring K/V)")
        if spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        self.spec_k = int(spec_k)
        if isinstance(draft_placement, str):
            draft_placement = emt_for_corner(draft_placement)
        self.draft_cfg = self.cfg.replace(emt=draft_placement)
        self.draft_params = params if draft_params is None else draft_params
        self.draft_step_params = with_weight_stats(
            self.draft_params, self.draft_cfg.placement,
            lm.tied_weights(self.draft_cfg))
        # contiguous shadow cache — the draft never pages (its placement is
        # dense digital; the paged machinery belongs to the target)
        self.draft_cache = lm.init_cache(self.draft_cfg, batch_size, max_len)
        self._draft_chunk = jax.jit(
            make_chunk_step(self.draft_cfg, mesh, rules), donate_argnums=(1,))
        self._draft_decode = jax.jit(
            make_serve_decode_step(self.draft_cfg, mesh, rules),
            donate_argnums=(1,))
        self._draft_zero = jax.jit(ServingEngine._zero_slot,
                                   donate_argnums=(0,))
        if self.paged:
            self._verify = jax.jit(
                make_verify_step(self.cfg, mesh, rules, self.page_lens),
                donate_argnums=(1,), static_argnames=("view_len",))
        else:
            self._verify = jax.jit(make_verify_step(self.cfg, mesh, rules),
                                   donate_argnums=(1,))
        # draft-side ledger (subset of the combined totals) + accept stats
        self.draft_total_energy_pj = 0.0
        self.draft_idle_energy_pj = 0.0
        self.draft_steps = 0
        self.spec_rounds = 0
        self.spec_proposed_total = 0
        self.spec_accepted_total = 0
        # accept_len_hist[L] = verify rounds that accepted exactly L drafts
        self.accept_len_hist = np.zeros(self.spec_k + 1, np.int64)
        # slots whose draft cache is one position behind: a fully-accepted
        # round commits the bonus token (verify lane k), whose *predecessor*
        # d_k the draft proposed but never decoded — so d_k's K/V at
        # position pos-1 is missing from the shadow cache and would never be
        # rewritten (the next round's writes start at pos).  Such slots get
        # a one-lane catch-up chunk write before their next draft.
        self._draft_lag: dict = {}

    # -- request surface -----------------------------------------------------
    def validate(self, req) -> np.ndarray:
        prompt = super().validate(req)
        if req.temperature != 0:
            raise ValueError("SpeculativeEngine is greedy-only: the "
                             "acceptance rule compares argmaxes "
                             f"(got temperature={req.temperature})")
        return prompt

    @property
    def accept_rate(self) -> float:
        return self.spec_accepted_total / max(1, self.spec_proposed_total)

    # -- metrics -------------------------------------------------------------
    def reset_metrics(self):
        super().reset_metrics()
        self.draft_total_energy_pj = 0.0
        self.draft_idle_energy_pj = 0.0
        self.draft_steps = 0
        self.spec_rounds = 0
        self.spec_proposed_total = 0
        self.spec_accepted_total = 0
        self.accept_len_hist[:] = 0

    def metrics(self) -> dict:
        m = super().metrics()
        m.update(
            draft_total_energy_pj=float(self.draft_total_energy_pj),
            draft_idle_energy_pj=float(self.draft_idle_energy_pj),
            draft_steps=int(self.draft_steps),
            spec_rounds=int(self.spec_rounds),
            spec_proposed_total=int(self.spec_proposed_total),
            spec_accepted_total=int(self.spec_accepted_total),
            accept_rate=float(self.accept_rate),
            accept_len_hist=[int(v) for v in self.accept_len_hist],
        )
        return m

    # -- draft-side bookkeeping ----------------------------------------------
    def _book_draft_step(self, eaux, rows, prefill_rows=frozenset()) -> float:
        """Book one draft-placement step into the combined ledger (so the
        engine-wide conservation invariant spans both placements) and into
        the draft-side split counters.  `rows` are the participating slot
        ids; the idle rows' share accrues to idle_energy_pj exactly like a
        target step."""
        self._steps += 1
        self.draft_steps += 1
        kv = float(eaux["kv_reads"])
        self.kv_reads_total += kv
        self.shard_kv_reads[0] += kv
        e = float(eaux["energy_pj"])
        self._book_corners(eaux["corners"])
        self.total_energy_pj += e
        self.shard_energy_pj[0] += e
        self.draft_total_energy_pj += e
        self.shard_occupancy[0] += len(rows)
        share = e / self.batch_size
        idle = share * (self.batch_size - len(rows))
        self.idle_energy_pj += idle
        self.shard_idle_energy_pj[0] += idle
        self.draft_idle_energy_pj += idle
        for i in rows:
            s = self.scheduler.slots[i]
            s.draft_energy_pj += share
            if i in prefill_rows:
                s.prefill_energy_pj += share
            else:
                s.energy_pj += share
        return share

    # -- the speculative round -----------------------------------------------
    def _chunk_advance(self, active) -> List[GenResult]:
        # prefill lanes ride the verify chunk (see class docstring): the
        # wide mixed step is never run, decode co-tenants keep speculating
        # through admissions
        return self._decode_advance(active)

    def _decode_advance(self, active) -> List[GenResult]:
        """One unified draft-k / verify-one round.

        Per decode slot: up to ``k_eff`` draft tokens are proposed by
        sequential greedy decodes on the draft placement (``k_eff`` clamps k
        to the slot's remaining token and cache budget, so verify writes
        never overrun the admission-time block reservation), then the target
        runs one (k+1)-lane verify chunk ``[last_token, d_1 .. d_k_eff]`` at
        the slot's exact positions and the longest greedy-matching draft
        prefix plus one target token is committed.  Rejected lanes' K/V is
        overwritten before any later query can attend it (write ranges are
        contiguous from each round's start and a chunk's queries never look
        past its own write frontier).

        A slot still streaming its prompt instead fills its lanes with the
        next <= k+1 prompt tokens (no drafts, no acceptance bookkeeping);
        the round that reaches the prompt's end commits the last lane's
        argmax as the first generated token, exactly like the wide chunk
        step's final-chunk sampling at temperature 0."""
        k = self.spec_k
        B, C = self.batch_size, self.spec_k + 1
        keff = {}
        prefill_take = {}
        for i, s in active:
            if s.prefilling:
                prefill_take[i] = min(C, len(s.prompt) - s.pos)
                keff[i] = 0
            else:
                total = min(len(s.prompt) + s.req.max_new - 1, self.max_len)
                remaining = s.req.max_new - len(s.generated)
                keff[i] = max(0, min(k, remaining - 1, total - 1 - s.pos))

        # ---- draft mirror: prefill lanes (write-for-write lockstep with
        # the target) and catch-up lanes for draft-cache holes left by
        # fully-accepted rounds (see _draft_lag), in one chunk call
        lag_rows = [i for i, s in active
                    if not s.prefilling and self._draft_lag.pop(i, False)]
        mirror_rows = sorted(set(prefill_take) | set(lag_rows))
        if mirror_rows:
            tokm = np.zeros((B, C), np.int32)
            posm = np.zeros(B, np.int32)
            ntokm = np.ones(B, np.int32)
            actm = np.zeros(B, bool)
            for i in mirror_rows:
                s = self.scheduler.slots[i]
                actm[i] = True
                if i in prefill_take:
                    take = prefill_take[i]
                    tokm[i, :take] = s.prompt[s.pos:s.pos + take]
                    posm[i] = s.pos
                    ntokm[i] = take
                else:
                    tokm[i, 0] = s.generated[-2]
                    posm[i] = s.pos - 1
            zerosm = np.zeros(B, np.int32)
            step_seed = self.seed + self._steps + 1 if self.fresh_noise \
                else self.seed
            _, self.draft_cache, eaux = self._draft_chunk(
                self.draft_step_params, self.draft_cache, jnp.asarray(tokm),
                jnp.asarray(posm), jnp.asarray(ntokm),
                jnp.asarray(actm), jnp.uint32(step_seed),
                jnp.asarray(zerosm.astype(np.uint32)), jnp.asarray(zerosm),
                jnp.zeros(B, jnp.float32), jnp.asarray(zerosm),
                jnp.ones(B, jnp.float32))
            self._book_draft_step(eaux, mirror_rows,
                                  frozenset(prefill_take))

        # ---- draft phase: sequential greedy proposals on the cheap corner
        drafts = {i: [] for i, _ in active}
        cur_tok = np.zeros(B, np.int32)
        cur_pos = np.zeros(B, np.int32)
        for i, s in active:
            cur_tok[i] = s.last_token
            cur_pos[i] = s.pos
        zeros_i = np.zeros(B, np.int32)
        for j in range(max(keff.values(), default=0)):
            rows = [i for i, _ in active if keff[i] > j]
            if not rows:
                break
            act = np.zeros(B, bool)
            act[rows] = True
            step_seed = self.seed + self._steps + 1 if self.fresh_noise \
                else self.seed
            next_tok, self.draft_cache, eaux = self._draft_decode(
                self.draft_step_params, self.draft_cache, jnp.asarray(cur_tok),
                jnp.asarray(cur_pos), jnp.asarray(act),
                jnp.uint32(step_seed), jnp.asarray(zeros_i.astype(np.uint32)),
                jnp.asarray(zeros_i), jnp.zeros(B, jnp.float32),
                jnp.asarray(zeros_i), jnp.ones(B, jnp.float32),
                jnp.asarray(zeros_i))
            self._book_draft_step(eaux, rows)
            next_tok = np.asarray(next_tok)
            for i in rows:
                t = int(next_tok[i])
                drafts[i].append(t)
                cur_tok[i] = t
                cur_pos[i] += 1

        # ---- verify phase: one all-lane chunk step on the analog target
        tokens = np.zeros((B, C), np.int32)
        start = np.zeros(B, np.int32)
        ntok = np.ones(B, np.int32)
        act = np.zeros(B, bool)
        for i, s in active:
            act[i] = True
            start[i] = s.pos
            if i in prefill_take:
                take = prefill_take[i]
                tokens[i, :take] = s.prompt[s.pos:s.pos + take]
                ntok[i] = take
            else:
                row = [s.last_token] + drafts[i]
                tokens[i, :len(row)] = row
                ntok[i] = len(row)
        self.peak_concurrent = max(self.peak_concurrent, len(active))
        extra, kwargs = (), {}
        if self.paged:
            # prefill lanes write inside the admission-time prompt
            # allocation; only decode lanes can cross into reserved blocks
            for i, s in active:
                if i in prefill_take:
                    continue
                for p in range(s.pos, s.pos + int(ntok[i])):
                    if self.scheduler.kv_ensure(i, p):
                        self._tables_dev = None
            extra, kwargs = self._paged_tables(
                [int(max(start[i] + ntok[i] for i, _ in active))])
        step_seed = self.seed + self._steps + 1 if self.fresh_noise \
            else self.seed
        # greedy: (B, C) per-lane target argmax
        greedy, share = self._run_step(active, lambda: self._verify(
            self.step_params, self.cache, jnp.asarray(tokens), jnp.asarray(start),
            jnp.asarray(ntok), jnp.asarray(act), jnp.uint32(step_seed),
            *extra, **kwargs))
        share = float(share[0])

        # ---- host-side acceptance + commit
        finished = []
        for i, s in active:
            if i in prefill_take:
                take = prefill_take[i]
                s.prefill_energy_pj += share
                s.pos += take
                self.prefill_tokens_total += take
                if not s.prefilling:    # prompt done: last lane's argmax is
                    t = int(greedy[i, take - 1])    # the first greedy token
                    s.last_token = t
                    s.generated.append(t)
                    self._emit(s.rid, t)
                done = self._maybe_retire(i)
                if done is not None:
                    finished.append(done)
                continue
            s.energy_pj += share
            s.steps += 1
            m = keff[i]
            L = 0
            while L < m and drafts[i][L] == int(greedy[i, L]):
                L += 1
            # accepted drafts + the target's token for the first mismatching
            # lane (a correction when L < m, a free bonus token when L == m)
            commit = drafts[i][:L] + [int(greedy[i, L])]
            s.spec_proposed += m
            s.spec_accepted += L
            self.spec_proposed_total += m
            self.spec_accepted_total += L
            self.accept_len_hist[L] += 1
            self.spec_rounds += 1
            committed = 0
            for t in commit:
                s.pos += 1
                s.last_token = t
                s.generated.append(t)
                self._emit(s.rid, t)
                committed += 1
                if s.req.eos_id is not None and t == s.req.eos_id:
                    break
            if L == m and m > 0 and committed == len(commit):
                # full accept: the bonus token's predecessor d_m was never
                # draft-decoded, so its K/V is missing at pos-1 — schedule
                # the catch-up write for this slot's next draft round
                self._draft_lag[i] = True
            done = self._maybe_retire(i)
            if done is not None:
                finished.append(done)
        return finished

    # -- retirement hygiene --------------------------------------------------
    def _retire(self, slot_id: int, reason: str) -> GenResult:
        # the shadow cache gets the same zero-on-retire hygiene as the
        # target: a backfilled slot must never attend the previous
        # request's draft K/V (including rejected-draft residue)
        self._draft_lag.pop(slot_id, None)
        self.draft_cache = self._draft_zero(self.draft_cache,
                                            jnp.int32(slot_id))
        return super()._retire(slot_id, reason)
