"""Hybrid device+CPU adaptive sampling.

Re-design of the reference's ``MixedGraphSageSampler``/``SampleJob``
(srcs/python/quiver/pyg/sage_sampler.py:180-376): daemon CPU worker
processes drain a task queue (cpu_sampler_worker_loop, sage_sampler.py:198-205)
while the device samples inline; every epoch the task split between device
and CPU is re-decided from measured average sample times
(decide_task_num, sage_sampler.py:272-288).

TPU mapping: "device" sampling is the XLA pipeline on the chip (which is
also busy training, so shifting sampling work to host CPUs is exactly as
valuable as it was on GPU); "CPU" sampling is the native host engine
(`quiver_tpu.csrc`). Workers are SPAWNED processes (fork deadlocks under
the JAX runtime's threads) attaching the CSR arrays — and per-edge weights,
when weighted — through POSIX shared memory, replacing the reference's
torch shared memory (CSRTopo.share_memory_, utils.py:216-226). Queues are
strictly per-worker with daemon drainer threads feeding one in-process
inbox, so a worker death can never wedge the train loop; dead workers'
pending tasks are resubmitted to survivors and the pool re-heals at the
next epoch.
"""

from __future__ import annotations

import multiprocessing as mp
import queue as queue_mod
import time
from typing import Iterator, Optional, Sequence

import numpy as np

from ..utils import CSRTopo
from .sage_sampler import DenseSample, GraphSageSampler

# sentinel a worker (or shutdown) posts on its result queue so the parent's
# drainer thread retires instead of blocking on get() forever
_DRAIN_DONE = ("__qt_drain_done__",)


class SampleJob:
    """Abstract indexable, shuffleable task list (reference
    sage_sampler.py:180-195). Each task is a seed batch."""

    def __getitem__(self, index: int):
        raise NotImplementedError

    def shuffle(self) -> None:
        raise NotImplementedError

    def __len__(self) -> int:
        raise NotImplementedError


class TrainSampleJob(SampleJob):
    """Canonical job: shuffle train ids, fixed-size seed batches."""

    def __init__(self, train_idx: np.ndarray, batch_size: int, seed: int = 0):
        self.train_idx = np.asarray(train_idx, np.int64).copy()
        self.batch_size = batch_size
        self._rng = np.random.default_rng(seed)

    def shuffle(self) -> None:
        self._rng.shuffle(self.train_idx)

    def __len__(self) -> int:
        return (len(self.train_idx) + self.batch_size - 1) // self.batch_size

    def __getitem__(self, index: int):
        lo = index * self.batch_size
        return self.train_idx[lo : lo + self.batch_size]


def _cpu_worker_loop(shm_names, shapes, sizes, caps, seed, task_q, result_q,
                     weights_shm=None):
    """Reference cpu_sampler_worker_loop (sage_sampler.py:198-205).

    Workers are spawned (fork deadlocks under the JAX runtime's threads) and
    attach the CSR arrays through POSIX shared memory — the analog of the
    reference sharing CSRTopo via torch shm (utils.py:216-226).
    ``weights_shm``: optional (name, shape) of a float32 per-edge weight
    array — workers then draw through the native weighted engine."""
    from multiprocessing import shared_memory

    import jax

    from ..ops.cpu_kernels import HostSampler

    # the parent holds the chip, and a chip belongs to one process. Nothing
    # imported on the way here touches a device (pinned by
    # tests/test_mixed_sampler.py), and the loop below is numpy + the native
    # engine; should a later change reach for JAX here, it gets the CPU
    # instead of hanging on the parent's chip.
    jax.config.update("jax_platforms", "cpu")

    shms = [shared_memory.SharedMemory(name=n) for n in shm_names]
    indptr = np.ndarray(shapes[0], dtype=np.int64, buffer=shms[0].buf)
    indices = np.ndarray(shapes[1], dtype=np.int64, buffer=shms[1].buf)
    weights = None
    if weights_shm is not None:
        shms.append(shared_memory.SharedMemory(name=weights_shm[0]))
        weights = np.ndarray(weights_shm[1], np.float32, buffer=shms[-1].buf)
    eng = HostSampler(indptr, indices, weights=weights)
    try:
        while True:
            item = task_q.get()
            if item is None:
                return
            epoch, task_idx, seeds = item
            t0 = time.perf_counter()
            n_id, count, adjs = eng.sample_multilayer(
                np.asarray(seeds, np.int64), sizes, seed + epoch * 1009 + task_idx, caps
            )
            dt = time.perf_counter() - t0
            result_q.put((epoch, task_idx, n_id, count, adjs, dt))
    finally:
        try:
            result_q.put(_DRAIN_DONE)  # retire the parent's drainer thread
        except Exception:
            pass
        del eng, indptr, indices, weights
        for shm in shms:
            shm.close()


class MixedGraphSageSampler:
    """Adaptive device+CPU k-hop sampler (reference sage_sampler.py:207-376).

    mode: "TPU_CPU_MIXED" | "HOST_CPU_MIXED" | "TPU_ONLY" | "CPU_ONLY"
    (reference spellings GPU_CPU_MIXED / UVA_CPU_MIXED / GPU_ONLY /
    UVA_ONLY accepted).

    Iterating yields ``(task_idx, DenseSample)`` per task, one epoch per
    ``__iter__`` (job reshuffled each epoch like the reference).
    """

    MODE_ALIASES = {
        "GPU_CPU_MIXED": "TPU_CPU_MIXED",
        "UVA_CPU_MIXED": "HOST_CPU_MIXED",
        "GPU_ONLY": "TPU_ONLY",
        "UVA_ONLY": "TPU_ONLY",
    }

    def __init__(
        self,
        job: SampleJob,
        csr_topo: CSRTopo,
        sizes: Sequence[int],
        num_workers: int = 2,
        device: int = 0,
        mode: str = "TPU_CPU_MIXED",
        caps: Optional[Sequence[Optional[int]]] = None,
        seed: int = 0,
        auto_tune_workers: bool = False,
        device_share_target: float = 0.5,
        weighted: bool = False,
        max_deg: int = 512,
    ):
        mode = self.MODE_ALIASES.get(mode, mode)
        if mode not in ("TPU_CPU_MIXED", "HOST_CPU_MIXED", "TPU_ONLY", "CPU_ONLY"):
            raise ValueError(f"unsupported mode: {mode}")
        if mode == "CPU_ONLY" and num_workers < 1:
            raise ValueError("CPU_ONLY mode needs num_workers >= 1")
        if weighted and csr_topo.edge_weights is None:
            raise ValueError(
                "weighted=True needs CSRTopo(edge_weights=...) "
                "(per-edge weights aligned with the COO input)"
            )
        if weighted and mode == "TPU_CPU_MIXED" and num_workers > 0:
            # the TPU engine weights only each row's first max_deg edges
            # (its static lane window), the CPU engine weights ALL edges —
            # on a graph whose max degree exceeds max_deg, device-assigned
            # and CPU-assigned tasks would draw from different
            # distributions. HOST_CPU_MIXED is exempt: its "device" half
            # is the host native engine, which also weights all edges.
            graph_max_deg = int(np.max(np.diff(csr_topo.indptr))) if len(
                csr_topo.indptr) > 1 else 0
            if graph_max_deg > max_deg:
                raise ValueError(
                    f"weighted MIXED sampling needs max_deg >= the graph's "
                    f"max degree ({graph_max_deg}; got max_deg={max_deg}): "
                    f"the device engine weights only the first max_deg edges "
                    f"per row while CPU workers weight all edges, so the two "
                    f"halves of one epoch would sample different "
                    f"distributions. Raise max_deg, or use CPU_ONLY/TPU_ONLY."
                )
        if num_workers > 0 and ("MIXED" in mode or mode == "CPU_ONLY"):
            # fail HERE with the real reason (and build the library once,
            # in the parent): otherwise every spawned worker dies on
            # HostSampler's RuntimeError in a detached process and the
            # parent only sees a 120 s "workers stalled" timeout
            from ..ops.cpu_kernels import require_native

            require_native("MixedGraphSageSampler's CPU workers")
        self.job = job
        self.csr_topo = csr_topo
        self.sizes = tuple(int(s) for s in sizes)
        self.caps = None if caps is None else tuple(caps)
        self.num_workers = num_workers if "MIXED" in mode or mode == "CPU_ONLY" else 0
        self.mode = mode
        self.seed = seed
        self.weighted = bool(weighted)
        dev_mode = "HOST" if mode.startswith("HOST") else "TPU"
        self.device_sampler = (
            None
            if mode == "CPU_ONLY"
            else GraphSageSampler(
                csr_topo, sizes, device=device, mode=dev_mode, caps=caps,
                seed=seed, weighted=weighted, max_deg=max_deg,
            )
        )
        self._workers = []
        self._task_qs = None
        self._result_qs = None
        self._inbox = None
        # measured averages drive the adaptive split (reference
        # avg_device_time/avg_cpu_time, sage_sampler.py:262-270)
        self.avg_device_time = 0.0
        self.avg_cpu_time = 0.0
        self.auto_tune_workers = auto_tune_workers and "MIXED" in mode
        self.device_share_target = float(device_share_target)
        self.last_device_share = None  # measured split of the last epoch

    # -- worker lifecycle (reference lazy_init, sage_sampler.py:298-313) ----
    def _spawn_worker(self, slot: int) -> None:
        """Start (or REPLACE, with fresh queues — the dead one's may be
        poisoned) the worker in ``slot``, plus its DRAINER thread.

        The parent never reads a worker pipe directly: a producer killed
        mid-put leaves a PARTIAL message on which even ``get_nowait`` blocks
        forever (poll() sees data, ``_recv_bytes`` never completes —
        measured, see tests/test_mixed_sampler.py worker-death tests). Each
        worker's results are pumped by a daemon thread into one thread-safe
        in-process inbox; if a drainer wedges on a torn message it strands
        only that daemon thread, never the train loop."""
        import threading

        ctx = mp.get_context("spawn")
        self._task_qs[slot] = ctx.Queue()
        result_q = ctx.Queue()
        self._result_qs[slot] = result_q
        self._spawn_count = getattr(self, "_spawn_count", 0) + 1
        shm_names, shapes, weights_shm = self._worker_shm_args
        p = ctx.Process(
            target=_cpu_worker_loop,
            args=(
                shm_names,
                shapes,
                self.sizes,
                self.caps,
                self.seed + 7919 * self._spawn_count,
                self._task_qs[slot],
                result_q,
                weights_shm,
            ),
            daemon=True,
        )
        p.start()
        self._workers[slot] = p

        inbox = self._inbox

        def drain():
            try:
                while True:
                    item = result_q.get()
                    if item == _DRAIN_DONE:
                        return  # worker exited (or shutdown retired us)
                    inbox.put(item)
            except Exception:
                return  # queue closed/poisoned: this drainer retires

        threading.Thread(target=drain, daemon=True).start()

    def lazy_init(self) -> None:
        if self.num_workers == 0:
            return
        if self._workers:
            # heal the pool: respawn any worker that died (OOM-kill etc.)
            # so one bad epoch does not degrade every later one
            for slot, p in enumerate(self._workers):
                if not p.is_alive():
                    self._spawn_worker(slot)
            return
        from multiprocessing import shared_memory

        # ONE task queue AND one result queue per worker (the reference
        # round-robins per-worker queues, sage_sampler.py:306-311) — and the
        # failure-isolation property this build adds: a process killed while
        # using an mp.Queue can corrupt that queue (documented
        # multiprocessing hazard), so nothing may be SHARED between workers
        # — a death then poisons only the dead worker's own queues, and
        # worker-death recovery can reroute pending tasks to survivors
        self._task_qs = [None] * self.num_workers
        self._result_qs = [None] * self.num_workers
        self._workers = [None] * self.num_workers
        self._inbox = queue_mod.Queue()  # thread queue: uncorruptible
        self._shms = []
        shm_names, shapes = [], []
        arrays = [
            (self.csr_topo.indptr, np.int64),
            (self.csr_topo.indices, np.int64),
        ]
        if self.weighted:
            arrays.append((self.csr_topo.edge_weights, np.float32))
        for arr, dt in arrays:
            arr = np.ascontiguousarray(arr, dt)
            shm = shared_memory.SharedMemory(create=True, size=max(arr.nbytes, 1))
            np.ndarray(arr.shape, dt, buffer=shm.buf)[:] = arr
            self._shms.append(shm)
            shm_names.append(shm.name)
            shapes.append(arr.shape)
        weights_shm = (shm_names[2], shapes[2]) if self.weighted else None
        self._worker_shm_args = (shm_names[:2], shapes[:2], weights_shm)
        for w in range(self.num_workers):
            self._spawn_worker(w)

    def shutdown(self) -> None:
        if self._task_qs is not None:
            for q, p in zip(self._task_qs, self._workers):
                if p.is_alive():
                    q.put(None)
        for p in self._workers:
            p.join(timeout=5)
            if p.is_alive():
                p.terminate()
        # retire drainer threads of TERMINATED workers (a clean worker exit
        # already posted the sentinel itself); a drainer wedged on a torn
        # message from a killed worker stays parked — daemon, harmless
        for q in self._result_qs or []:
            try:
                q.put(_DRAIN_DONE)
            except Exception:
                pass
        # never let interpreter exit JOIN these queues' feeder threads: a
        # dead worker's task queue can hold unread buffered items (pipe
        # full, no reader), wedging multiprocessing's atexit finalizer
        # forever (reproduced: 12-passed suite hanging at _exit_function)
        for q in (self._task_qs or []) + (self._result_qs or []):
            try:
                q.cancel_join_thread()
            except Exception:
                pass
        self._workers = []
        self._task_qs = None
        self._result_qs = None
        self._inbox = None
        for shm in getattr(self, "_shms", []):
            try:
                shm.close()
                shm.unlink()
            except Exception:
                pass
        self._shms = []

    # -- adaptive split (reference decide_task_num, sage_sampler.py:272-288)
    def decide_task_num(self, total: int) -> int:
        """Number of tasks the device takes this epoch."""
        if self.mode == "CPU_ONLY":
            return 0
        if self.num_workers == 0 or self.mode == "TPU_ONLY":
            return total
        if self.avg_device_time <= 0 or self.avg_cpu_time <= 0:
            # first epoch: split evenly to get measurements
            return max(total // 2, 1)
        device_rate = 1.0 / self.avg_device_time
        cpu_rate = self.num_workers / self.avg_cpu_time
        share = device_rate / (device_rate + cpu_rate)
        return int(round(total * share))

    def _update_avg(self, attr: str, dt: float) -> None:
        prev = getattr(self, attr)
        setattr(self, attr, dt if prev == 0 else 0.9 * prev + 0.1 * dt)

    def suggest_num_workers(
        self,
        device_share_target: Optional[float] = None,
        max_workers: Optional[int] = None,
    ) -> int:
        """Worker count that pushes the device's task share down to
        ``device_share_target`` given the measured per-task averages.

        The device competes with TRAINING for the same chip (the reason the
        hybrid sampler exists, reference sage_sampler.py:207-230), so a
        lower device share frees step time; more workers only help while
        host cores are spare. From ``share = dev_rate/(dev_rate+cpu_rate)``
        and ``cpu_rate = w/avg_cpu``: ``w = avg_cpu*(1-t)/(t*avg_dev)``.
        """
        import os as _os

        t = self.device_share_target if device_share_target is None else device_share_target
        if self.avg_device_time <= 0 or self.avg_cpu_time <= 0 or not 0 < t < 1:
            return self.num_workers
        if max_workers is None:
            max_workers = max(_os.cpu_count() or 1, 1)
        w = self.avg_cpu_time * (1.0 - t) / (t * self.avg_device_time)
        return int(np.clip(round(w), 1, max_workers))

    def _maybe_retune_workers(self) -> None:
        """auto_tune_workers: re-spawn the worker pool between epochs when
        the measured averages call for a different size (the feedback loop
        the reference leaves manual)."""
        if not self.auto_tune_workers:
            return
        want = self.suggest_num_workers()
        if want != self.num_workers and self._workers:
            self.shutdown()
            self.num_workers = want

    def _to_dense(self, n_id, count, adjs) -> DenseSample:
        import jax.numpy as jnp

        from .sage_sampler import DenseAdj

        dense_adjs = tuple(
            DenseAdj(
                cols=jnp.asarray(a["cols"]),
                mask=jnp.asarray(a["mask"]),
                n_src=jnp.asarray(a["n_src"], jnp.int32),
                n_dst=jnp.asarray(a["n_dst"], jnp.int32),
            )
            for a in adjs[::-1]
        )
        return DenseSample(
            n_id=jnp.asarray(n_id),
            count=jnp.asarray(count, jnp.int32),
            batch_size=int(adjs[0]["n_dst"]) if adjs else 0,
            adjs=dense_adjs,
        )

    # -- epoch iterator (reference iter_sampler, sage_sampler.py:316-368) ---
    def __iter__(self) -> Iterator:
        self._maybe_retune_workers()
        self.lazy_init()
        self.job.shuffle()
        # stale-epoch fencing: an abandoned iterator (break/GeneratorExit)
        # may leave this epoch's tasks in flight; results are tagged with the
        # epoch and anything older is discarded on receipt
        self._epoch = getattr(self, "_epoch", 0) + 1
        epoch = self._epoch
        total = len(self.job)
        device_num = self.decide_task_num(total)
        self.last_device_share = device_num / max(total, 1)

        # per-task completion tracking enables WORKER-FAILURE RECOVERY (the
        # reference has none — a dead worker's in-flight task hung its
        # epoch): duplicates from resubmission are dropped on receipt
        pending: set = set(range(device_num, total))
        # EPOCH-scoped recovery state (inside recv_blocking it would reset
        # per call and re-trigger resubmission storms): the alive watermark,
        # the last PROGRESS stamp (refreshed on every received result — a
        # healthy-but-slow pool is not idle), and a 10 s floor between
        # steals bounding duplicated work
        recover = {
            "last_alive": len(self._workers),
            "last_progress": time.monotonic(),
            "last_resubmit": time.monotonic(),
        }

        def recv(block: bool):
            """Next NEW CPU result of THIS epoch from the drainer inbox, or
            None when nothing arrives (after ~2 s when blocking). The inbox
            is an in-process thread queue — worker death cannot corrupt it
            (the per-worker pipes are only ever read by disposable daemon
            drainer threads, see _spawn_worker)."""
            deadline = time.monotonic() + (2.0 if block else 0.0)
            while True:
                try:
                    timeout = max(deadline - time.monotonic(), 0.0)
                    item = self._inbox.get(timeout=timeout) if timeout else (
                        self._inbox.get_nowait()
                    )
                except queue_mod.Empty:
                    return None
                r_epoch, task_idx, n_id, count, adjs, dt = item
                if r_epoch != epoch or task_idx not in pending:
                    continue  # stale epoch, or duplicate after resubmit
                pending.discard(task_idx)
                recover["last_progress"] = time.monotonic()
                self._update_avg("avg_cpu_time", dt)
                return task_idx, self._to_dense(n_id, count, adjs)

        def submit(tasks):
            """Round-robin tasks over ALIVE workers' queues (the reference's
            per-worker dispatch, sage_sampler.py:306-311; per-worker queues
            also mean a killed worker cannot poison a sibling's queue)."""
            targets = [
                q for q, p in zip(self._task_qs, self._workers) if p.is_alive()
            ]
            if not targets:
                raise RuntimeError(
                    "all CPU sampler workers died (see worker stderr); "
                    f"{len(pending)} task(s) unfinished"
                )
            for i, t in enumerate(tasks):
                targets[i % len(targets)].put(
                    (epoch, t, np.asarray(self.job[t], np.int64))
                )

        def recv_blocking():
            """recv with failure recovery: if a worker DIED while tasks are
            pending — or the tail has been idle for a while (one slow
            worker hoarding its round-robin share) — every pending task is
            resubmitted round-robin to the live workers; duplicate answers
            are filtered in recv. If the whole pool is dead, fail
            immediately with the real reason instead of a long stall."""
            start = time.monotonic()
            while True:
                res = recv(block=True)
                if res is not None:
                    return res
                alive = sum(p.is_alive() for p in self._workers)
                if alive == 0:
                    raise RuntimeError(
                        "all CPU sampler workers died (see worker stderr); "
                        f"{len(pending)} task(s) unfinished"
                    )
                now = time.monotonic()
                died = alive < recover["last_alive"]
                # steal only when NOTHING has arrived for an idle window
                # (slow-but-healthy pools keep refreshing last_progress in
                # recv), rate-limited to the same window; the window scales
                # with the measured per-task time — capped at 90 s — so
                # legitimately slow tasks (huge fanouts, loaded host) don't
                # trigger resubmit storms, and the stall deadline scales
                # with the window so the steal always gets to fire first
                idle_s = min(max(10.0, 3.0 * self.avg_cpu_time), 90.0)
                idle_steal = (
                    now - recover["last_progress"] > idle_s
                    and now - recover["last_resubmit"] > idle_s
                )
                if died or idle_steal:
                    submit(sorted(pending))
                    recover["last_alive"] = alive
                    recover["last_resubmit"] = now
                if now - start > max(120.0, 4.0 * idle_s):
                    raise TimeoutError("CPU sampler workers stalled")

        try:
            if pending:
                submit(range(device_num, total))
            for t in range(device_num):
                t0 = time.perf_counter()
                ds = self.device_sampler.sample_dense(self.job[t])
                import jax

                jax.block_until_ready(ds.n_id)
                self._update_avg("avg_device_time", time.perf_counter() - t0)
                yield t, ds
                # drain any finished CPU results between device tasks
                while pending:
                    res = recv(block=False)
                    if res is None:
                        break
                    yield res
            while pending:
                yield recv_blocking()
        except Exception:
            # drain workers so the queue doesn't wedge (the reference's only
            # recovery logic, sage_sampler.py:361-368)
            self.shutdown()
            raise

    def __del__(self):
        try:
            self.shutdown()
        except Exception:
            pass
