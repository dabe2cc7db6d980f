"""What every driver shares: the hosted service, the set-up probe of the
first train steps, the window with its profiler, and the comparisons with
the plain reference."""
from __future__ import annotations

import shutil
import statistics
import sys
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import checks, configs, program, traffic
from chipbench import trace as tr

WAIT_S = 900.0        # bound on any one wait of set-up; covers a cold compile


class BenchFailure(RuntimeError):
    """The run cannot go on; it prints no result."""


def wait_for(pred: Callable[[], bool], what: str, app: Any = None,
             timeout_s: float = WAIT_S, poll_s: float = 0.002) -> None:
    deadline = time.monotonic() + timeout_s
    while not pred():
        if app is not None and not app.healthy():
            raise BenchFailure(f"{what}: app unhealthy "
                               f"({getattr(app, '_failure', None)!r})")
        if time.monotonic() > deadline:
            raise BenchFailure(f"{what}: not reached in {timeout_s:.0f}s")
        time.sleep(poll_s)


def profile_options():
    """Device and host activity, without the Python call tracer (it would
    record every Python call of the window and slow the host)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def mark(name: str):
    """A host annotation in the profiler's trace (``bench/<name>``)."""
    return jax.profiler.TraceAnnotation(tr.MARK_PREFIX + name)


class Ctx:
    """One run: its arguments, cell, configuration and what it found."""

    def __init__(self, args, t_start: float,
                 workload: Optional[Dict[str, Any]] = None,
                 config: Optional[Dict[str, Any]] = None):
        self.args = args
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(int(args.trace))
        self.t_start = t_start
        self.workload = workload or configs.workload(args.workload)
        self.config = config or configs.config(self.workload["config"])
        self.limits: Dict[str, float] = self.workload.get("limits", {})
        self.e2e: Dict[str, float] = {}
        self.record: Dict[str, Any] = {}
        self.compared: List[List[Any]] = []     # [name, value, limit]
        self.attempted = 0
        self.failed = 0
        self.setup_s: Optional[float] = None
        self.memory_peak: Optional[int] = None
        self.window_t0 = self.window_t1 = 0.0
        self.trace_dir = str(configs.ROOT / "chipbench_out" / "trace"
                             / f"{args.workload}-{self.seed}")

    # -- comparisons ------------------------------------------------------
    def compare(self, name: str, value: float) -> None:
        if name not in self.limits:
            raise BenchFailure(f"no limit for {name!r} in the workload")
        self.compared.append([name, float(value), float(self.limits[name])])

    def correct(self) -> bool:
        return bool(self.compared) and all(
            np.isfinite(v) and v <= lim for _, v, lim in self.compared)

    # -- the measured window ---------------------------------------------
    def begin_window(self) -> None:
        self.setup_s = time.perf_counter() - self.t_start
        self.log("set-up done")
        if self.trace:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            jax.profiler.start_trace(self.trace_dir,
                                     profiler_options=profile_options())
        self.window_t0 = time.perf_counter()
        self.window_mono0 = time.monotonic()

    def end_window(self) -> None:
        self.window_t1 = time.perf_counter()
        self.window_mono1 = time.monotonic()
        if self.trace:
            jax.profiler.stop_trace()
            self._read_trace()
        self.log("window done")

    def log(self, what: str) -> None:
        print(f"[chipbench {time.perf_counter() - self.t_start:8.2f}s] "
              f"{what}", file=sys.stderr, flush=True)

    def _read_trace(self) -> None:
        path = tr.find_xplane(self.trace_dir)
        per_dev = tr.device_ops(path)
        marks = tr.host_marks(path)
        window_ns = int((self.window_t1 - self.window_t0) * 1e9)
        busy = [tr.busy_ns(ops) for ops in per_dev.values()]
        ops = [e for evs in per_dev.values() for e in evs]
        self.record["trace"] = {
            "ops": ops, "busy_s": statistics.mean(busy) / 1e9,
            "window_s": window_ns / 1e9,
            "top_ops": tr.top_ops(ops),
            "idle_gaps": tr.idle_gaps(next(iter(per_dev.values())), marks)}
        shutil.rmtree(self.trace_dir, ignore_errors=True)

    def read_peak(self) -> None:
        peaks = []
        for d in jax.local_devices():
            stats = d.memory_stats() or {}
            if "peak_bytes_in_use" in stats:
                peaks.append(int(stats["peak_bytes_in_use"]))
        self.memory_peak = max(peaks) if peaks else None

    def spans_in_window(self, name: str) -> List[Dict[str, Any]]:
        """Finished ``obs`` spans of this name that began in the window,
        with their durations converted to wall seconds by the installed
        clock's ``scale`` (spans are stamped in paper seconds)."""
        from repro.obs.trace import tracer
        from repro.sim.simtime import active_clock
        scale = active_clock().scale
        lo, hi = self.window_mono0, self.window_mono1
        return [{"name": s.name, "dur_s": s.duration * scale,
                 "args": dict(s.args)}
                for s in tracer().spans(name=name)
                if lo <= s.t0 * scale <= hi]


# ------------------------------------------------------------ the service

def make_service():
    from repro.ckpt.storage import InMemoryStore
    from repro.clusters import LocalBackend
    from repro.core import CACSService
    return CACSService({"local": LocalBackend(1)},
                       {"default": InMemoryStore()})


def live_device_bytes() -> int:
    return int(sum(x.nbytes for x in jax.live_arrays()))


def save_window(ctx: Ctx, svc, coord, app, saves) -> List[int]:
    """The measured window: runs for ``--seconds`` while the harness asks
    the service for an async save (``trigger_checkpoint``, the daemon's
    call) every ``period_s`` from ``first_s``, so that every run has its
    saves at the same points. A save that falls due while the previous
    one is still being written first waits for its commit, so the app
    never holds two pinned states (``period_s`` is set above the save
    time; ``late_saves`` counts such waits). Returns the image steps of
    the saves asked for; failed saves count in ``ctx.failed``."""
    ctx.begin_window()
    t_end = ctx.window_t0 + ctx.seconds
    due = ctx.window_t0 + saves["first_s"] if saves else float("inf")
    steps: List[int] = []
    late = 0
    while (now := time.perf_counter()) < t_end and app.healthy():
        if now >= due:
            with mark("save"):
                err = svc.ckpt.wait(coord, strict=False)
                late += int(time.perf_counter() - now > 0.005)
                ctx.failed += int(err is not None)
                steps.append(svc.trigger_checkpoint(coord.coord_id,
                                                    blocking=False))
            due += saves["period_s"]
            continue
        time.sleep(min(due, t_end, now + 0.1) - now)
    ctx.end_window()
    ctx.record["late_saves"] = late
    return steps


def log_saves(ctx: Ctx) -> None:
    """The window's pin and save times, on standard error."""
    for name, spans in ctx.record.get("spans", {}).items():
        ctx.log(f"{name}: " + " ".join(f"{s['dur_s']:.3f}s" for s in spans))
    ctx.log(f"late saves: {ctx.record.get('late_saves', 0)}")


# --------------------------------------------------------- train set-up

class TrainProbe:
    """Reads the hosted trainer's state as its first steps go by: each
    leaf's first gradient as the optimizer takes it (first moment over
    1 - b1, after one step) and the weights after ``steps`` steps."""

    def __init__(self, b1: float, steps: int):
        self.b1, self.steps = b1, steps
        self.app = None
        self.g1: Optional[Dict[str, float]] = None
        self.p_last: Optional[Dict[str, np.ndarray]] = None
        # set to an empty dict to take the state the next step starts from
        self.capture: Optional[Dict[str, np.ndarray]] = None

    def __call__(self, k: int) -> None:
        if self.app is None:
            return
        if self.capture is not None and not self.capture:
            self.capture.update(checks.host_tree(
                {"state": self.app.checkpoint_state()["state"]}))
        if k != 1 and k != self.steps:
            return
        state = self.app.checkpoint_state()["state"]
        if k == 1 and self.g1 is None:
            m = checks.path_items(state["opt_state"]["m"])
            norms = _norms(list(m.values()))
            self.g1 = {n: float(v) / (1 - self.b1)
                       for n, v in zip(m, jax.device_get(norms))}
        if k == self.steps and self.p_last is None:
            self.p_last = checks.host_tree(state["params"])


@jax.jit
def _norms(xs):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for x in xs]


def adamw_config(opt: Dict[str, Any]):
    from repro.train import AdamWConfig
    return AdamWConfig(lr=opt["lr"], b1=opt["b1"], b2=opt["b2"],
                       eps=opt["eps"], weight_decay=opt["weight_decay"],
                       grad_clip=opt["grad_clip"],
                       warmup_steps=opt["warmup_steps"],
                       schedule=opt["schedule"], total_steps=10 ** 9)


def trainer_factory(ctx: Ctx, probe: TrainProbe, streams: List[Any]):
    """A factory of hosted trainers fed by the benchmark's own stream."""
    from repro.train.trainer import TrainerApp
    wl, cfg = ctx.workload, ctx.config
    arch = program.arch(cfg)
    opt = adamw_config(wl["optimizer"])

    def make():
        app = TrainerApp(arch, global_batch=wl["batch"],
                         seq_len=wl["seq_len"], n_steps=10 ** 9, opt=opt,
                         seed=ctx.seed)
        stream = traffic.TokenStream(ctx.seed, wl["batch"], wl["seq_len"],
                                     cfg["vocab_size"], on_batch=probe)
        app.pipeline = stream
        probe.app = app
        streams.append(stream)
        return app
    return make


def window_rate(stamps: List[float], per_step: float) -> Optional[float]:
    """Work per second between the first and last step boundary."""
    if len(stamps) < 2:
        return None
    return (len(stamps) - 1) * per_step / (stamps[-1] - stamps[0])


# ------------------------------------------------- the model-step check

def reference_steps(ctx: Ctx, lowp: bool = False, rows: int = 0):
    """The plain reference over the cell's first ``check_steps`` batches:
    (losses, first-gradient norms, initial weights, final weights). With
    ``rows``, only each batch's first rows (a planted fault: part of the
    batch left out, the mean taken over the rest)."""
    wl, cfg = ctx.workload, ctx.config
    ref = configs.reference(cfg["reference"])
    batches = [traffic.lm_batch(ctx.seed, k, wl["batch"], wl["seq_len"],
                                cfg["vocab_size"])
               for k in range(wl["check_steps"])]
    if rows:
        batches = [{k: v[:rows] for k, v in b.items()} for b in batches]
    return ref.train_steps(cfg, wl["optimizer"], ctx.seed, batches,
                           lowp=lowp)


def step_gaps(ref, losses: List[float], g1: Dict[str, float],
              p_last: Dict[str, np.ndarray]) -> Dict[str, float]:
    """Gaps of a run's first steps from the reference's: the worst step's
    loss gap, the worst leaf's gap between the norms of the first gradient,
    and the worst leaf's gap between the norms of the weights' change over
    the steps. A leaf's gap is measured against the larger of its
    reference norm and the median leaf's; leaves whose reference gradient
    is under 1e-3 of the median leaf's are left out of the change (they
    move by round-off alone)."""
    r_losses, r_g1, p0, r_pn = ref
    n = len(r_losses)
    if g1 is None or p_last is None or len(losses) < n:
        raise BenchFailure("the first steps were not observed")
    out = {"loss_gap": max(abs(a - b) for a, b in zip(losses[:n],
                                                        r_losses))}
    med_g = statistics.median(r_g1.values())
    out["grad_gap"] = max(abs(g1[k] - r_g1[k]) / max(r_g1[k], med_g)
                          for k in r_g1)
    p0, r_pn = checks.path_items(p0), checks.path_items(r_pn)
    keep = [k for k in r_g1 if r_g1[k] >= 1e-3 * med_g]
    f32 = lambda x: np.asarray(x, np.float32)               # noqa: E731
    r_dn = {k: float(np.linalg.norm(f32(r_pn[k]) - f32(p0[k])))
            for k in keep}
    p_dn = {k: float(np.linalg.norm(f32(p_last[k]) - f32(p0[k])))
            for k in keep}
    med_d = statistics.median(r_dn.values())
    out["change_gap"] = max(abs(p_dn[k] - r_dn[k]) / max(r_dn[k], med_d)
                            for k in keep)
    return out


def check_train_steps(ctx: Ctx, losses: List[float],
                      probe: TrainProbe) -> None:
    """Compare the hosted run's first steps with the plain reference."""
    ctx.record["reference"] = ref = reference_steps(ctx)
    gaps = step_gaps(ref, losses, probe.g1, probe.p_last)
    for name, v in gaps.items():
        ctx.compare(name, v)
    ctx.log("reference done")


def control_train_steps(ctx: Ctx, rows: int = 0) -> Dict[str, float]:
    """The same gaps, read with the low-precision reference in the
    program's place (against the run's own reference, where it ran); with
    ``rows``, the full-precision reference on part of each batch."""
    ref = ctx.record.get("reference") or reference_steps(ctx)
    ctl = reference_steps(ctx, lowp=not rows, rows=rows)
    return step_gaps(ref, ctl[0], ctl[1], checks.path_items(ctl[3]))
