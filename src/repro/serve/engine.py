"""Continuous-batching serving engine over a fixed slot axis.

The engine holds ``slots`` concurrent sequences in one cache (dense or
paged, see :mod:`repro.serve.cache`) and runs generation as a stream of
identical jitted dispatches:

* **admit** — one fused-prefill dispatch per request
  (:func:`repro.models.transformer.forward_prefill_cached`): the whole
  prompt in one trunk pass, cache scattered in place of a freed slot,
  first token sampled from the last-position logits. Compiled once per
  distinct prompt length (prompts are never padded: padding would
  corrupt recurrent-mixer state and leave attendable garbage KV rows).
* **step** — one decode dispatch advancing *every* slot by one token.
  Each slot carries its own position, so :func:`decode_step` (whose
  index is a shared scalar) is ``vmap``-ed over the slot axis with a
  per-slot index — the per-slot math is exactly the single-sequence
  decode path, which is what makes engine output token-identical to the
  token-by-token baseline (test-enforced).

Requests are admitted from an arrival queue into freed slots *as
sequences finish* — no generation barrier — so short requests stop
occupying compute the moment they are done (``admission='static'``
restores the barrier for A/B benchmarking). All shapes are static:
slot count, cache layout, and table width never change, so the decode
step stays one compiled program regardless of the admission schedule.
"""
from __future__ import annotations

import collections
import time
from dataclasses import dataclass
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import transformer as T
from repro.serve.cache import is_group_path, make_ops


@dataclass
class Request:
    """One generation request: prompt tokens + a token budget.

    ``deadline`` (optional) bounds the request's total time in the
    system, measured from ``arrival`` on the serve timeline (seconds
    under ``wall_clock=True``, decode steps otherwise). A slot still
    generating when its deadline passes is *evicted*: the partial
    sequence is returned (``Result.evicted == "deadline"``) and the slot
    and its pages are freed for the arrival queue — graceful degradation
    under overload instead of head-of-line blocking."""
    rid: int
    tokens: np.ndarray          # (P,) int32 prompt
    max_new: int                # tokens to generate (>= 1)
    arrival: float = 0.0        # seconds after serve() start
    deadline: Optional[float] = None  # max time in system, from arrival


@dataclass
class Result:
    rid: int
    tokens: np.ndarray          # (P + generated,) prompt + generated
    prompt_len: int
    arrival: float
    t_admit: float
    t_finish: float
    logits: Optional[List[np.ndarray]] = None
    # None = ran to its own max_new; "deadline" = wall-clock eviction;
    # "budget" = hit the engine-wide token_budget cap first
    evicted: Optional[str] = None

    @property
    def latency(self) -> float:
        return self.t_finish - self.arrival


@dataclass
class _Slot:
    active: bool = False        # occupancy flag (rid values are caller-owned)
    rid: int = 0
    length: int = 0             # tokens absorbed so far == next write index
    max_new: int = 0
    generated: int = 0
    last_tok: int = 0
    n_pages: int = 0
    budget: int = 0             # min(max_new, engine token_budget)
    expiry: float = float("inf")  # absolute eviction time on the timeline


class ServeEngine:
    """Continuous-batching generation over a merged (non-split) model.

    params: ``{'client': ..., 'server': ...}`` single-client layout, as
    produced by :func:`repro.models.transformer.init_params` or by
    merging a federated checkpoint (see :mod:`repro.api.serving`).
    """

    def __init__(self, params, cfg, *, slots: int = 4, max_len: int = 256,
                 pages: int = 0, page_size: int = 16,
                 temperature: float = 0.0, seed: int = 0,
                 admission: str = "continuous", record_logits: bool = False,
                 token_budget: Optional[int] = None):
        if not cfg.is_decoder:
            raise ValueError("ServeEngine requires a decoder arch")
        if cfg.frontend is not None:
            raise ValueError("ServeEngine serves text-only archs "
                             f"(frontend={cfg.frontend!r})")
        if admission not in ("continuous", "static"):
            raise ValueError(f"unknown admission mode {admission!r}")
        self.params = params
        self.cfg = cfg
        self.slots = slots
        self.max_len = max_len
        self.temperature = float(temperature)
        self.admission = admission
        self.record_logits = record_logits
        # engine-wide cap on generated tokens per request (overload
        # protection): a request whose max_new exceeds it is evicted at
        # the cap with Result.evicted == "budget"
        if token_budget is not None and token_budget < 1:
            raise ValueError(f"token_budget must be >= 1, got {token_budget}")
        self.token_budget = token_budget
        # sampling stream, folded off the raw seed key so it never
        # collides with the param-init stream PRNGKey(seed)
        self._key = jax.random.fold_in(jax.random.PRNGKey(seed), 1)

        from repro.models.common import dtype_of
        self.ops = make_ops(cfg, slots, max_len, dtype_of(cfg.dtype),
                            pages=pages, page_size=page_size)

        # vmapped per-slot decode: strip the slot axis, run decode_step
        # at B=1 with this slot's own index, restore the slot axis.
        axes = jax.tree_util.tree_map_with_path(
            lambda p, _: 1 if is_group_path(p) else 0,
            jax.eval_shape(lambda: T.init_decode_cache(
                cfg, slots, max_len, dtype_of(cfg.dtype))))

        def one(params, tok, idx, cache1):
            cb = jax.tree_util.tree_map_with_path(
                lambda p, a: a[:, None] if is_group_path(p) else a[None],
                cache1)
            logits, nc = T.decode_step(
                params, {"tokens": tok[None, None]}, cb, idx, cfg)
            nc = jax.tree_util.tree_map_with_path(
                lambda p, a: a[:, 0] if is_group_path(p) else a[0], nc)
            return logits[0, 0], nc

        slot_decode = jax.vmap(one, in_axes=(None, 0, 0, axes),
                               out_axes=(0, axes))

        # params are an argument of every jitted dispatch, never a
        # closed-over constant: at full width a captured copy would be
        # embedded in each executable (GBs of constants per compile)
        def step_fn(params, cache, table, toks, idxs, ctr):
            dense = self.ops.gather(cache, table)
            logits, new_dense = slot_decode(params, toks, idxs, dense)
            logits = logits.astype(jnp.float32)
            nxt = self._pick(logits, ctr)
            return self.ops.scatter(cache, new_dense, table, idxs), nxt, logits

        self._step = jax.jit(step_fn, donate_argnums=(1,))
        self._admits: Dict[int, object] = {}  # prompt_len -> jitted admit

        # host-side bookkeeping
        self._cache = self.ops.init()
        self._table = np.full((slots, self.ops.max_pages), -1, np.int32)
        self._free_pages = list(range(pages - 1, -1, -1)) if pages else []
        self._free_slots = list(range(slots - 1, -1, -1))
        self._slot = [_Slot() for _ in range(slots)]
        self._out: Dict[int, list] = {}
        self._log: Dict[int, list] = {}
        self._admit_meta: Dict[int, tuple] = {}
        self._results: Dict[int, Result] = {}
        self._wave_open = True
        self._ctr = 0

    # -- sampling ----------------------------------------------------------

    def _pick(self, logits, ctr):
        """Greedy or temperature sampling; traced inside the jitted fns."""
        if self.temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        key = jax.random.fold_in(self._key, ctr)
        return jax.random.categorical(
            key, logits / self.temperature, axis=-1).astype(jnp.int32)

    def _admit_fn(self, prompt_len: int):
        fn = self._admits.get(prompt_len)
        if fn is None:
            def admit_fn(params, cache, prompt, table_row, slot, ctr):
                logits, req = T.forward_prefill_cached(
                    params, {"tokens": prompt}, self.cfg, self.max_len)
                cache = self.ops.admit(cache, req, table_row, slot)
                lg = logits[0, 0].astype(jnp.float32)
                return cache, self._pick(lg[None], ctr)[0], lg
            fn = self._admits[prompt_len] = jax.jit(
                admit_fn, donate_argnums=(1,))
        return fn

    # -- scheduling --------------------------------------------------------

    @property
    def n_active(self) -> int:
        return self.slots - len(self._free_slots)

    def _try_admit(self, req: Request, now: float,
                   results: Dict[int, Result]) -> bool:
        if not self._free_slots:
            return False
        n_pages = 0
        if self.ops.paged:
            n_pages = self.ops.pages_needed(len(req.tokens) + req.max_new)
            if n_pages > len(self._free_pages):
                return False
        slot = self._free_slots.pop()
        row = np.full((self.ops.max_pages,), -1, np.int32)
        for j in range(n_pages):
            row[j] = self._free_pages.pop()
        self._table[slot] = row

        prompt = jnp.asarray(req.tokens[None].astype(np.int32))
        self._cache, tok0, lg = self._admit_fn(len(req.tokens))(
            self.params, self._cache, prompt, jnp.asarray(row), jnp.int32(slot),
            jnp.int32(self._ctr))
        self._ctr += 1
        tok0 = int(tok0)

        s = self._slot[slot]
        s.active = True
        s.rid, s.length, s.max_new = req.rid, len(req.tokens), req.max_new
        s.generated, s.last_tok, s.n_pages = 1, tok0, n_pages
        s.budget = (req.max_new if self.token_budget is None
                    else min(req.max_new, self.token_budget))
        s.expiry = (float("inf") if req.deadline is None
                    else req.arrival + req.deadline)
        self._out[req.rid] = [tok0]
        if self.record_logits:
            self._log[req.rid] = [np.asarray(lg)]
        self._admit_meta[req.rid] = (req, now)
        if s.generated >= s.budget:
            self._finish(slot, now, results,
                         "budget" if s.budget < s.max_new else None)
        return True

    def _finish(self, slot: int, now: float, results: Dict[int, Result],
                evicted: Optional[str] = None):
        s = self._slot[slot]
        req, t_admit = self._admit_meta.pop(s.rid)
        self._free_pages.extend(
            int(p) for p in self._table[slot][:s.n_pages])
        self._table[slot] = -1
        self._free_slots.append(slot)
        results[s.rid] = Result(
            rid=s.rid,
            tokens=np.concatenate([req.tokens.astype(np.int32),
                                   np.asarray(self._out.pop(s.rid), np.int32)]),
            prompt_len=len(req.tokens), arrival=req.arrival,
            t_admit=t_admit, t_finish=now,
            logits=self._log.pop(s.rid, None), evicted=evicted)
        s.active = False

    def _evict_expired(self, now: float, results: Dict[int, Result]) -> int:
        """Free every slot whose request deadline has passed; returns the
        count evicted. The partial sequence generated so far is returned
        as the request's result (``evicted == "deadline"``)."""
        n = 0
        for slot, s in enumerate(self._slot):
            if s.active and now >= s.expiry:
                self._finish(slot, now, results, "deadline")
                n += 1
        return n

    def _step_once(self, now: float, results: Dict[int, Result]):
        toks = np.array([s.last_tok for s in self._slot], np.int32)
        idxs = np.array([s.length for s in self._slot], np.int32)
        self._cache, nxt, logits = self._step(
            self.params, self._cache, jnp.asarray(self._table), jnp.asarray(toks),
            jnp.asarray(idxs), jnp.int32(self._ctr))
        self._ctr += 1
        nxt = np.asarray(nxt)
        if self.record_logits:
            logits = np.asarray(logits)
        for slot, s in enumerate(self._slot):
            if not s.active:
                continue
            s.length += 1
            s.generated += 1
            s.last_tok = int(nxt[slot])
            self._out[s.rid].append(s.last_tok)
            if self.record_logits:
                self._log[s.rid].append(logits[slot])
            if s.generated >= s.budget:
                self._finish(slot, now, results,
                             "budget" if s.budget < s.max_new else None)

    # -- public API --------------------------------------------------------

    def admit(self, req: Request, now: float = 0.0) -> bool:
        """Prefill one request into a free slot (one fused dispatch).
        False if no slot (or, paged, not enough free pages) is available."""
        total = len(req.tokens) + req.max_new
        if req.max_new < 1:
            raise ValueError(f"request {req.rid}: max_new must be >= 1")
        if total > self.max_len:
            raise ValueError(
                f"request {req.rid}: {total} tokens > max_len={self.max_len}")
        return self._try_admit(req, now, self._results)

    def step(self, now: float = 0.0) -> None:
        """Advance every active slot by one token (one decode dispatch).
        Slots past their request deadline are evicted first, not
        stepped."""
        self._evict_expired(now, self._results)
        if self.n_active:
            self._step_once(now, self._results)

    def take_finished(self) -> Dict[int, Result]:
        """Pop and return the requests finished since the last call."""
        out, self._results = self._results, {}
        return out

    def serve(self, requests: List[Request], *,
              wall_clock: bool = True) -> Dict[int, Result]:
        """Run a batch of requests to completion. Arrivals are honoured
        on the wall clock (``wall_clock=False`` treats every request as
        already arrived — deterministic, for tests)."""
        for r in requests:
            total = len(r.tokens) + r.max_new
            if r.max_new < 1:
                raise ValueError(f"request {r.rid}: max_new must be >= 1")
            if total > self.max_len:
                raise ValueError(
                    f"request {r.rid}: {total} tokens > max_len={self.max_len}")
            if r.deadline is not None and r.deadline <= 0:
                raise ValueError(
                    f"request {r.rid}: deadline must be > 0, "
                    f"got {r.deadline}")
        pending = collections.deque(
            sorted(requests, key=lambda r: (r.arrival, r.rid)))
        results: Dict[int, Result] = {}
        t0 = time.monotonic()

        while pending or self.n_active:
            now = (time.monotonic() - t0) if wall_clock else float(self._ctr)
            # deadline evictions free slots BEFORE admission, so a queued
            # request can take over an expired slot this very iteration
            self._evict_expired(now, results)
            if self.n_active == 0:
                self._wave_open = True  # static mode: new admission wave
            arrived = bool(pending) and (not wall_clock
                                         or pending[0].arrival <= now)
            may_admit = (self.admission == "continuous" or self._wave_open)
            if arrived and may_admit:
                if self._try_admit(pending[0], now, results):
                    pending.popleft()
                    continue
                if self.n_active == 0:
                    raise RuntimeError(
                        "page pool too small for a single request — "
                        "raise ServeSpec.pages")
            if self.n_active:
                self._wave_open = False
                self._step_once(now, results)
            elif pending and wall_clock:
                time.sleep(min(0.01, max(0.0, pending[0].arrival - now)))
        return results

    def generate(self, prompts: np.ndarray, max_new: int) -> np.ndarray:
        """Batch convenience wrapper: all prompts arrive at t=0; returns
        (B, P + max_new) prompt+generated tokens, row i = prompt i."""
        prompts = np.asarray(prompts)
        reqs = [Request(i, prompts[i], max_new) for i in range(len(prompts))]
        res = self.serve(reqs, wall_clock=False)
        return np.stack([res[i].tokens for i in range(len(prompts))])

    def warmup(self, prompt_lens: List[int]):
        """Compile the admit dispatches for the given prompt lengths and
        the shared decode step, so serving latency excludes compile."""
        for P in prompt_lens:
            req = Request(rid=-(P + 1), tokens=np.zeros((P,), np.int32),
                          max_new=2)
            self.serve([req], wall_clock=False)

    def state_bytes(self) -> int:
        """Resident decode-cache bytes (pool budget when paged)."""
        return self.ops.state_bytes()
