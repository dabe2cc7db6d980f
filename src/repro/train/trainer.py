"""Training loop + the CACS-hosted TrainerApp.

``make_train_step`` builds the jitted (and, under a mesh, fully sharded)
train step that ``TrainerApp``, the launchers and the chip benchmark run.

``TrainerApp`` adapts a JAX training job to the CACS Application protocol —
the 2026 analogue of the paper's long-running MPI application: it is
checkpointed/suspended/migrated by the service without knowing how, and its
health hook reports NaN losses and stalls (paper §6.3: only the application
knows what "healthy" means).
"""
from __future__ import annotations

import threading
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt.layout import PreEncodedLeaf
from repro.ckpt.plane import PreEncodedChunk
from repro.ckpt.snapshot import DeferredSnapshot, SnapshotHandle
from repro.configs.base import ArchConfig
from repro.data.pipeline import TokenPipeline
from repro.obs.telemetry import SampleView, registry, unique_name
from repro.obs.trace import tracer
from repro.kernels.qsnap import qsnap_encode_chunks
from repro.models.model import Model, build_model
from repro.sharding.specs import MeshAxes, activation_sharding
from repro.sim.simtime import active_clock
from repro.train.optimizer import (AdamWConfig, adamw_init, adamw_update,
                                   opt_state_dims)


def init_state(model: Model, key: jax.Array) -> Dict[str, Any]:
    params = model.init(key)
    return {"params": params, "opt_state": adamw_init(params),
            "step": jnp.zeros((), jnp.int32)}


def state_dims(model: Model) -> Dict[str, Any]:
    pd = model.param_dims()
    return {"params": pd, "opt_state": opt_state_dims(pd), "step": ()}


def make_train_step(model: Model, opt_cfg: AdamWConfig, *,
                    axes: Optional[MeshAxes] = None, remat: bool = True,
                    grad_specs=None):
    """Returns train_step(state, batch) -> (state, metrics).

    ``grad_specs``: optional PartitionSpec tree for the gradients. Pinning
    grads to the param sharding right at the autodiff boundary lets SPMD
    emit reduce-scatters instead of full all-reduces for FSDP-sharded
    weight grads (§Perf MoE iteration: 2.7GB AR -> 170MB RS per layer).
    """

    def train_step(state, batch):
        with activation_sharding(axes):
            (loss, aux), grads = jax.value_and_grad(
                lambda p: model.loss(p, batch, remat=remat),
                has_aux=True)(state["params"])
            if grad_specs is not None:
                grads = jax.lax.with_sharding_constraint(grads, grad_specs)
            params, opt_state, om = adamw_update(
                opt_cfg, grads, state["opt_state"], state["params"])
        metrics = {"loss": loss, **aux, **om}
        return ({"params": params, "opt_state": opt_state,
                 "step": state["step"] + 1}, metrics)

    return train_step


def _device_encodable(x: Any) -> bool:
    """Leaves the device encode stage can handle: single-shard jax arrays
    (a sharded leaf would need per-shard chunk framing — those fall back
    to the host path, which handles shards natively)."""
    if not isinstance(x, jax.Array):
        return False
    try:
        return len(x.sharding.device_set) == 1
    except Exception:                              # noqa: BLE001
        return False


def encode_state_on_device(tree: Any, *, impl: Optional[str] = None,
                           interpret: bool = False) -> Any:
    """Replace array leaves with device-encoded ``QS01`` payloads.

    Runs ``kernels.qsnap.qsnap_encode_chunks`` over every single-shard
    jax.Array leaf: quantization happens on the accelerator, the D2H
    copy carries int8 codes + scales (~4x fewer bytes than f32), and the
    resulting ``PreEncodedLeaf``s flow through the writer's pass-through
    encode stage. Payloads are byte-identical to the host "int8" codec,
    so the image dedups and restores exactly like a host-compressed one.
    Non-array leaves (python scalars in iterator state) pass through and
    are framed losslessly by the host codec.
    """
    flat, treedef = jax.tree_util.tree_flatten(tree)
    idx = [i for i, x in enumerate(flat) if _device_encodable(x)]
    payloads = qsnap_encode_chunks([flat[i] for i in idx], impl=impl,
                                   interpret=interpret)
    for i, payload in zip(idx, payloads):
        x = flat[i]
        chunk = PreEncodedChunk(payload, "int8")
        flat[i] = PreEncodedLeaf(
            shape=tuple(x.shape), dtype=str(x.dtype),
            chunks=[((0,) * x.ndim, tuple(x.shape), chunk)])
    return jax.tree_util.tree_unflatten(treedef, flat)


class TrainerApp:
    """A real JAX training job hosted by CACS.

    Checkpoint state is {"state": {params, opt_state, step}, "data": iterator
    state} — restoring it resumes the exact token stream and optimizer
    trajectory (verified bit-exact in tests).
    """

    def __init__(self, cfg: ArchConfig, *, global_batch: int = 4,
                 seq_len: int = 64, n_steps: int = 50,
                 opt: Optional[AdamWConfig] = None, seed: int = 0,
                 remat: bool = True):
        self.cfg = cfg
        self.model = build_model(cfg)
        self.opt_cfg = opt or AdamWConfig(warmup_steps=5, total_steps=n_steps)
        self.n_steps = n_steps
        self.seed = seed
        self.pipeline = TokenPipeline(cfg, global_batch, seq_len, seed=seed)
        self._train_step = jax.jit(
            make_train_step(self.model, self.opt_cfg, remat=remat))
        self._state: Optional[Dict[str, Any]] = None
        self._state_lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.last_loss: float = float("nan")
        self.losses: list = []
        self.step_times: list = []
        # seconds the loop was blocked per snapshot pin: the registry
        # histogram is the store; ckpt_stalls (below) is a read-only view
        self._stall_hist = registry().histogram(
            unique_name("trainer.ckpt_stall_s"))
        self._host_step = 0                  # mirrors state["step"] host-side
        self.restarts = 0
        self._started = False
        # first train-step exception; healthy() flips False on it
        self._failure: Optional[BaseException] = None

    # ---- Application protocol ------------------------------------------
    def start(self, ctx, restore_state: Optional[Any]) -> None:
        if restore_state is not None:
            with self._state_lock:
                self._state = restore_state["state"]
                self.pipeline.load_state_dict(restore_state["data"])
                self._host_step = int(restore_state["data"]["step"])
            self.restarts += 1
        elif self._state is None:
            self._state = init_state(self.model, jax.random.PRNGKey(self.seed))
        self._stop.clear()
        self._failure = None
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()
        self._started = True

    def _run(self) -> None:
        clock = active_clock()
        while not self._stop.is_set() and self._host_step < self.n_steps:
            tr = tracer()
            with tr.span("train/step", cat="train"):
                t0 = clock.now()
                try:
                    with tr.span("train/input", cat="train"):
                        batch = self.pipeline.next()
                        batch = {k: jnp.asarray(v) for k, v in batch.items()}
                    with tr.span("train/dispatch", cat="train"):
                        new_state, metrics = self._train_step(self._state,
                                                              batch)
                    with tr.span("train/sync", cat="train"):
                        loss = float(metrics["loss"])
                        # join the step OUTSIDE the lock — a concurrent
                        # snapshot capture must never wait on device work
                        new_state = jax.block_until_ready(new_state)
                except Exception as e:             # noqa: BLE001
                    # A step that raises (e.g. device out of memory) ends
                    # the loop: healthy() turns False so the monitor
                    # recovers the job from its newest image, and
                    # is_done() turns True so no caller polls a dead loop
                    # forever. The stream rewinds to the failed batch, so
                    # a restart retries that batch.
                    with self._state_lock:
                        self.pipeline.step = self._host_step
                    self._failure = e
                    registry().inc("trainer.step_failures",
                                   note=f"{type(e).__name__}: {e}")
                    return
                with self._state_lock:
                    self._state = new_state
                    self._host_step += 1     # swap + count: one atomic unit
                self.last_loss = loss
                self.losses.append(loss)
                self.step_times.append(clock.now() - t0)

    @property
    def ckpt_stalls(self) -> "SampleView":
        """Per-snapshot pin stalls, as a list-like view over the registry
        histogram (len()/indexing kept for existing tests and examples)."""
        return SampleView(self._stall_hist)

    @property
    def current_step(self) -> int:
        # host-side mirror: reading it never forces a device sync (the
        # old int(state["step"]) stalled callers on the in-flight step)
        return self._host_step

    def checkpoint_state(self) -> Dict[str, Any]:
        with self._state_lock:
            state = self._state
            data = dict(self.pipeline.state_dict())
            data["step"] = self._host_step    # align stream with params
        return {"state": state, "data": data}

    def snapshot_async(self, *, step: Optional[int] = None,
                       codec: Optional[str] = None) -> SnapshotHandle:
        """Staged snapshot (Application protocol extension).

        Capture = pin the current state dict + iterator state under the
        lock (microseconds; jax arrays are immutable and ``_run`` swaps
        whole dicts, so references ARE a consistent snapshot). The
        device→host copy — or, when ``codec`` selects int8, the on-device
        qsnap encode — happens in ``resolve()`` on the checkpoint writer
        thread, overlapped with the next jitted step.
        """
        clock = active_clock()
        t0 = clock.now()
        with self._state_lock:
            state = self._state
            data = dict(self.pipeline.state_dict())
            data["step"] = host_step = self._host_step
        self._stall_hist.observe(clock.now() - t0)
        device_encode = codec in ("int8", "int8+zlib")

        def materialize():
            if device_encode:
                return {"state": encode_state_on_device(state), "data": data}
            return {"state": state, "data": data}

        return DeferredSnapshot(
            materialize, step=host_step if step is None else step)

    def healthy(self) -> bool:
        if self._failure is not None:
            return False
        if not self.losses:
            return True
        return bool(np.isfinite(self.last_loss))

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=60)

    def is_done(self) -> bool:
        """True once every step ran, or once a step failed (the loop is
        gone either way; healthy() tells the two apart)."""
        return self.current_step >= self.n_steps or self._failure is not None

    def progress(self) -> float:
        return self.current_step / max(self.n_steps, 1)
