"""Serving engine: prefill + decode with donated caches.

Also hosts ``ServeApp`` — a CACS-managed inference job whose checkpoint
state is {params, KV/SSM caches, generated tokens}: suspending a *serving*
job mid-generation and resuming it elsewhere (even on another "cloud") is
the paper's job-swapping use case applied to inference. A hybrid model's
cache holds both kinds of state side by side: attention K/V rows (bf16,
growing with the positions filled) and recurrent state (f32 ``h`` and the
conv tail, fixed per sequence). The decode, the pin and the restore carry
every leaf unchanged, whichever kind.
"""
from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt.snapshot import DeferredSnapshot, SnapshotHandle
from repro.configs.base import ArchConfig
from repro.models.model import Model, build_model
from repro.models.transformer import cache_bytes
from repro.obs.telemetry import SampleView, registry, unique_name
from repro.obs.trace import tracer
from repro.sim.simtime import active_clock


class Engine:
    def __init__(self, model: Model, params: Any, *, cache_len: int = 256):
        self.model = model
        self.params = params
        self.cache_len = cache_len
        self._decode = jax.jit(self._greedy_step, donate_argnums=(1,))
        self._prefill_row = jax.jit(self._into_row, donate_argnums=(1,))

    def _greedy_step(self, params, cache, token, pos):
        logits, cache = self.model.decode_step(params, cache, token, pos)
        return (jnp.argmax(logits, -1)[:, None].astype(jnp.int32), cache,
                pos + 1)

    def _into_row(self, params, cache, batch, row):
        logits, c = self.model.prefill(params, batch,
                                       cache_len=self.cache_len)
        return logits, jax.tree.map(
            lambda t, u: jax.lax.dynamic_update_slice_in_dim(
                t, u.astype(t.dtype), row, axis=1), cache, c)

    def prefill(self, batch: Dict[str, Any]):
        """Prefill the batch one row (slot) at a time into a batch cache
        from ``init_cache``: each row's K/V and recurrent state are written
        at its own row (every cache leaf has the batch on axis 1), so no
        activation ever spans the whole batch. One ``serve/prefill`` span
        holds a ``serve/prefill_row`` span per row. Returns (the first
        tokens [B,1], the cache)."""
        tr = tracer()
        tokens = batch["tokens"]
        rows = tokens.shape[0]
        cache = self.model.init_cache(rows, self.cache_len)
        firsts = []
        with tr.span("serve/prefill", cat="serve",
                     args={"rows": rows, "tokens": int(tokens.size)}):
            for r in range(rows):
                with tr.span("serve/prefill_row", cat="serve",
                             args={"row": r, "tokens": tokens.shape[1]}):
                    one = {k: jnp.asarray(v[r:r + 1])
                           for k, v in batch.items()}
                    logits, cache = self._prefill_row(
                        self.params, cache, one, jnp.int32(r))
                    firsts.append(jax.block_until_ready(
                        jnp.argmax(logits, -1).astype(jnp.int32)))
        return jnp.concatenate(firsts)[:, None], cache

    def decode(self, cache, token, pos):
        """One greedy decode step in one dispatch, the cache donated:
        (the next tokens [B,1] int32, the cache, ``pos + 1``), all left
        on the device."""
        return self._decode(self.params, cache, token, pos)

    def generate(self, batch: Dict[str, jax.Array], n_tokens: int,
                 *, greedy: bool = True) -> jax.Array:
        """Prefill the prompt then decode n_tokens greedily. Returns
        [B, n_tokens] int32."""
        prompt_len = batch["tokens"].shape[1]
        if self.model.cfg.frontend is not None \
                and self.model.cfg.family != "encdec":
            prompt_len += self.model.cfg.frontend_len
        token, cache = self.prefill(batch)
        pos = jnp.int32(prompt_len)
        out = [token]
        for _ in range(1, n_tokens):
            token, cache, pos = self.decode(cache, token, pos)
            out.append(token)
        return jnp.concatenate(out, axis=1)


class ServeApp:
    """CACS-hosted batched-serving job (checkpointable mid-generation)."""

    def __init__(self, cfg: ArchConfig, *, batch: int = 2,
                 prompt_len: int = 16, n_tokens: int = 64,
                 cache_len: int = 128, seed: int = 0,
                 token_delay_s: float = 0.0):
        self.cfg = cfg
        self.model = build_model(cfg)
        self.batch = batch
        self.prompt_len = prompt_len
        self.n_tokens = n_tokens
        self.cache_len = cache_len
        self.seed = seed
        self.token_delay_s = token_delay_s   # rate-limit (tests/demos)
        self.params: Any = None
        self.cache: Any = None
        # tokens in host memory; ``generated`` counts them and nothing else
        self.tokens_out: List[np.ndarray] = []
        self.generated = 0
        # the slot: the cache and the last token of the newest dispatched
        # step; ``_pending`` holds the tokens of dispatched steps not yet
        # fetched to the host, oldest first (at most two, for a moment)
        self._last_token = None
        self._pending: List[jax.Array] = []
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._lock = threading.Lock()
        # signaled whenever the donated-cache slot refills (or the decode
        # loop dies): _capture blocks on this instead of polling the clock
        self._cond = threading.Condition(self._lock)
        # first decode-loop exception; healthy() flips False on it
        self._failure: Optional[BaseException] = None
        # seconds decode was blocked per snapshot pin: registry histogram
        # is the store; ckpt_stalls (below) is a read-only view
        self._stall_hist = registry().histogram(
            unique_name("serve.ckpt_stall_s"))
        self.restarts = 0
        self._cache_nbytes: Dict[str, int] = {}

    def _build(self):
        if self.params is None:
            self.params = self.model.init(jax.random.PRNGKey(self.seed))
        self.engine = Engine(self.model, self.params,
                             cache_len=self.cache_len)

    def start(self, ctx, restore_state: Optional[Any]) -> None:
        if restore_state is not None:
            # the image's weights: no fresh init beside them on the device
            with self._lock:
                self.params = restore_state["params"]
                self.cache = restore_state["cache"]
                self.generated = int(restore_state["generated"])
                self._last_token = jnp.asarray(restore_state["last_token"])
                self.tokens_out = [np.asarray(restore_state["tokens_out"])] \
                    if self.generated else []
                self._pending = []
            self._publish_cache_bytes()
            self.restarts += 1
        self._build()
        self._stop.clear()
        self._failure = None
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _publish_cache_bytes(self) -> None:
        self._cache_nbytes = cache_bytes(self.model.blocks, self.cache)
        for kind, n in self._cache_nbytes.items():
            registry().set_gauge(f"serve.cache_bytes.{kind}", n)

    def cache_bytes(self) -> Dict[str, int]:
        """Bytes of the decode cache by kind (``attn`` K/V rows, ``ssm``
        recurrent state): what a pin copies on the device."""
        return dict(self._cache_nbytes)

    def _slot_count(self) -> int:
        """Tokens of the step the slot holds: delivered, plus dispatched
        and not yet fetched."""
        return self.generated + len(self._pending)

    def _run(self) -> None:
        if self.cache is None:
            rng = np.random.Generator(np.random.PCG64(self.seed))
            prompt = rng.integers(
                0, self.cfg.vocab_size, (self.batch, self.prompt_len)
            ).astype(np.int32)
            token, cache = self.engine.prefill({"tokens": prompt})
            with self._cond:
                self.cache = cache
                self._last_token = token
                self.tokens_out.append(np.asarray(token))
                self.generated = 1
                self._cond.notify_all()
            self._publish_cache_bytes()
        clock = active_clock()
        # the position stays on the device: each step returns the next one
        pos = jnp.int32(self.prompt_len + self.generated - 1)
        # One step in flight: step n+1 is dispatched with token n still on
        # the device, and token n is fetched while step n+1 runs, so the
        # device never waits for the host's round trip.
        while not self._stop.is_set() and self._slot_count() < self.n_tokens:
            if self.token_delay_s:
                clock.sleep(self.token_delay_s)
            tr = tracer()
            step = self._slot_count()
            overlapped = bool(self._pending)
            with tr.span("serve/step", cat="serve",
                         args={"generated": step, "overlapped": overlapped}):
                # NOTE: cache is donated; keep the swap atomic wrt
                # checkpointing
                with self._lock:
                    cache, token = self.cache, self._last_token
                    self.cache = None
                with tr.span("serve/dispatch", cat="serve"):
                    try:
                        token, new_cache, pos = self.engine.decode(
                            cache, token, pos)
                    except BaseException as e:     # noqa: BLE001
                        # Restore the surrendered slot: leaving it None
                        # would make every _capture (snapshot_async,
                        # suspend) block forever on a dead loop. The
                        # pre-decode cache is the last consistent state
                        # (best-effort — if the jitted call got far enough
                        # to consume the donated buffer, a later restore
                        # re-reads the newest committed image instead), so
                        # a suspend issued after the fault still swaps out
                        # cleanly. The tokens still in flight are
                        # delivered.
                        with self._cond:
                            self.cache = cache
                            self._failure = e
                            self._cond.notify_all()
                        registry().inc("serve.decode_failures",
                                       note=f"{type(e).__name__}: {e}")
                        self._deliver(0)
                        return
                if overlapped:
                    registry().inc("serve.decode_overlapped")
                with self._cond:
                    self.cache = new_cache
                    self._last_token = token
                    self._pending.append(token)
                    self._cond.notify_all()
                last = self._stop.is_set() or step + 1 >= self.n_tokens
                with tr.span("serve/sync", cat="serve"):
                    self._deliver(0 if last else 1)
        self._deliver(0)

    def _deliver(self, keep: int) -> None:
        """Fetch the oldest dispatched tokens to the host until ``keep``
        remain in flight; ``generated`` counts each one as it lands. Only
        the decode thread changes ``_pending``, so the fetch runs outside
        the lock: a pin never waits for the device."""
        while len(self._pending) > keep:
            host = np.asarray(self._pending[0])
            with self._cond:
                self._pending.pop(0)
                self.tokens_out.append(host)
                self.generated += 1
                self._cond.notify_all()

    def _capture(self) -> Dict[str, Any]:
        """Pin a consistent snapshot under the lock (waits out the window
        where the donated cache is surrendered to a decode's dispatch).
        Cache, last token, tokens and count are the newest dispatched
        step's: tokens not yet fetched go in as device arrays, which
        ``_materialize`` fetches, so the image's ``generated`` may be one
        ahead of the public counter.
        Params/tokens are references (never donated, immutable); the KV
        cache is **copied on device** — the very next decode step donates
        the live buffer, so a pinned reference would read as "Array has
        been deleted" by the time the writer thread encodes it. The copy
        is dispatch-only (async), so the pin stall stays in microseconds.

        Blocks on a condition variable signaled when the slot refills —
        never on the installed clock: a virtual-time poll here would race
        the SimClock forward while the decode runs in wall time (the same
        retime hazard the gang barrier's paused-rank poll had). The wait
        timeout is only a wall-clock backstop against a decode thread that
        dies without notifying."""
        with self._cond:
            while self.cache is None:
                if self._failure is not None:
                    raise RuntimeError(
                        "serve decode loop failed with the donated cache "
                        "unrecoverable") from self._failure
                self._cond.wait(timeout=0.1)
            return {
                "params": self.params,
                "cache": jax.tree_util.tree_map(
                    lambda x: jnp.array(x, copy=True)
                    if isinstance(x, jax.Array) else x, self.cache),
                "generated": self._slot_count(),
                "last_token": self._last_token,
                "tokens_out": self.tokens_out + self._pending,
            }

    @staticmethod
    def _materialize(snap: Dict[str, Any], batch: int) -> Dict[str, Any]:
        out = dict(snap)
        out["tokens_out"] = (np.concatenate(snap["tokens_out"], axis=1)
                             if snap["tokens_out"]
                             else np.zeros((batch, 0), np.int32))
        return out

    def checkpoint_state(self) -> Dict[str, Any]:
        return self._materialize(self._capture(), self.batch)

    def snapshot_async(self, *, step: Optional[int] = None,
                       codec: Optional[str] = None) -> SnapshotHandle:
        """Staged snapshot: capture pins params/cache/token references
        (token-latency stall only while a decode's dispatch holds the
        donated cache); the concat + any host copies run at ``resolve()``
        on the writer thread. The KV cache stays lossless regardless of
        ``codec`` — quantizing it would perturb the generated stream,
        and suspend/resume guarantees the tokens are unchanged."""
        clock = active_clock()
        t0 = clock.now()
        snap = self._capture()
        self._stall_hist.observe(clock.now() - t0)
        return DeferredSnapshot(
            lambda: self._materialize(snap, self.batch),
            step=snap["generated"] if step is None else step)

    @property
    def ckpt_stalls(self) -> SampleView:
        """Per-snapshot pin stalls, as a list-like view over the registry
        histogram (len()/indexing kept for existing callers)."""
        return SampleView(self._stall_hist)

    def healthy(self) -> bool:
        return self._failure is None

    def stop(self, join_s: float = 60.0) -> bool:
        """Stop the decode loop. Returns True when the thread LEAKED —
        the join timed out on a wedged decode (e.g. a hung device call).
        Leaks are counted in the ``serve.stop_timeouts`` registry counter
        with the last decode error as the note, so a fleet teardown that
        silently strands threads is visible in one telemetry snapshot."""
        self._stop.set()
        with self._cond:
            self._cond.notify_all()
        thread = self._thread
        if thread is None:
            return False
        thread.join(timeout=join_s)
        if thread.is_alive():
            registry().inc(
                "serve.stop_timeouts",
                note=f"decode thread wedged after {join_s}s "
                     f"(last_error={self._failure!r})")
            return True
        return False

    def is_done(self) -> bool:
        return self.generated >= self.n_tokens

    def progress(self) -> float:
        return self.generated / max(self.n_tokens, 1)
