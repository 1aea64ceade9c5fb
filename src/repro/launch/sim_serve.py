"""Async simulation service on the SimDriver protocol.

The seed's `launch/serve.py` served one jitted LM step behind a batching
loop; this is the same shape refactored onto simulations: jobs are
serialized `SimSpec` JSON, the queue buckets compatible jobs by
`spec_signature` (api.facade) into ONE `EnsembleSimulation` batch per
signature, windows run in a worker thread, and each job streams its
per-window diagnostic bundle back as it lands. Compiled window
executables are cached per signature (`ExecutableCache`, LRU) so a
repeat spec shape never re-traces — and evicting a signature drops its
executables with the cached callable.

Protocol (stdlib only — asyncio + JSON lines, no network deps):

    svc = SimService(max_batch=8, max_queue=64)
    await svc.start()
    job_id = await svc.submit(spec.to_json())
    async for event in svc.results(job_id):
        ...   # {"event": "window", ...} * N, then a terminal event:
        ...   # done | error | rejected (admission bound) | cancelled
    svc.cancel(job_id)   # queued -> dropped; running -> stream cut short
    await svc.close()

Optionally `serve(svc, host, port)` exposes the same protocol over a
JSON-lines TCP socket (one request object in, event stream out).

CLI smoke lane (CI runs this):

    python -m repro.launch.sim_serve --smoke
"""

from __future__ import annotations

import argparse
import asyncio
import itertools
import json
import sys
import time
from collections import OrderedDict
from dataclasses import dataclass, field

from repro.api.facade import (
    build_fields,
    build_particles,
    pic_config,
    spec_signature,
)
from repro.api.spec import SimSpec
from repro.launch.compile_cache import enable_compile_cache
from repro.pic.ensemble import EnsembleSimulation, member_bundle

__all__ = ["ExecutableCache", "SimJob", "SimService", "serve"]


class ExecutableCache:
    """Signature-keyed LRU of fresh jitted ensemble-window callables.

    Each entry owns its compiled executables (`make_ensemble_window_fn`
    returns an independent jit wrapper), so evicting the least recently
    used signature releases that shape bucket's compiled code — the
    service's memory ceiling is ``maxsize`` spec shapes, not the union of
    every spec it ever saw.
    """

    def __init__(self, maxsize: int = 8):
        if maxsize < 1:
            raise ValueError(f"maxsize must be >= 1, got {maxsize}")
        self.maxsize = maxsize
        self._entries: OrderedDict[str, object] = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get(self, signature: str):
        fn = self._entries.get(signature)
        if fn is not None:
            self.hits += 1
            self._entries.move_to_end(signature)
            return fn
        from repro.pic.ensemble import make_ensemble_window_fn

        self.misses += 1
        fn = make_ensemble_window_fn()
        self._entries[signature] = fn
        while len(self._entries) > self.maxsize:
            self._entries.popitem(last=False)
            self.evictions += 1
        return fn

    def stats(self) -> dict:
        return {
            "size": len(self._entries),
            "maxsize": self.maxsize,
            "hits": self.hits,
            "misses": self.misses,
            "evictions": self.evictions,
        }


@dataclass
class SimJob:
    """One submitted simulation: its spec, shape signature, and the event
    queue its client drains through `SimService.results`."""

    id: str
    spec: SimSpec
    signature: str
    status: str = "queued"
    events: asyncio.Queue = field(default_factory=asyncio.Queue)


class SimService:
    """Async job queue that batches same-signature specs into one
    compiled ensemble.

    The worker takes the oldest queued job, waits up to ``batch_wait``
    seconds for more jobs of the same signature (up to ``max_batch``),
    re-queues mismatches, and runs the batch as ONE `EnsembleSimulation`
    whose window callable comes from the signature-keyed
    `ExecutableCache`. Every fetched window bundle is streamed to each
    job's event queue as a ``window`` event; a terminal ``done`` (with
    final diagnostics + full history) or ``error`` event closes the
    stream.
    """

    def __init__(self, *, max_batch: int = 8, batch_wait: float = 0.05,
                 cache_size: int = 8, max_queue: int = 0):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if max_queue < 0:
            raise ValueError(f"max_queue must be >= 0, got {max_queue}")
        self.max_batch = max_batch
        self.batch_wait = batch_wait
        self.max_queue = max_queue  # admission bound; 0 = unbounded
        self.cache = ExecutableCache(cache_size)
        self.jobs: dict[str, SimJob] = {}
        self._pending: asyncio.Queue = asyncio.Queue()
        self._ids = itertools.count()
        self._worker: asyncio.Task | None = None
        self.batches_run = 0
        self.jobs_done = 0
        self.queued = 0      # jobs admitted but not yet running
        self.rejected = 0    # jobs refused at the admission bound
        self.cancelled = 0   # cancel() calls that hit a live job

    # -- client side --------------------------------------------------------

    async def start(self) -> None:
        if self._worker is None:
            self._worker = asyncio.get_running_loop().create_task(self._run_loop())

    async def submit(self, spec_json: str | dict) -> str:
        """Accept a serialized SimSpec (JSON string or dict); returns the
        job id to stream `results` from. Raises on malformed specs —
        bad input is the client's error, not the worker's."""
        spec = (
            SimSpec.from_dict(spec_json)
            if isinstance(spec_json, dict)
            else SimSpec.from_json(spec_json)
        )
        job = SimJob(
            id=f"job-{next(self._ids)}",
            spec=spec,
            signature=spec_signature(spec),
        )
        self.jobs[job.id] = job
        if self.max_queue and self.queued >= self.max_queue:
            # Admission control: refuse loudly instead of buffering without
            # bound — the client sees a terminal event, not a hang.
            job.status = "rejected"
            self.rejected += 1
            job.events.put_nowait({
                "event": "rejected",
                "job": job.id,
                "queued": self.queued,
                "max_queue": self.max_queue,
                "message": f"queue full ({self.queued}/{self.max_queue}); "
                           "retry after draining a result stream",
            })
            return job.id
        self.queued += 1
        await self._pending.put(job)
        return job.id

    def cancel(self, job_id: str) -> str:
        """Cancel a job: a queued job is dropped (terminal ``cancelled``
        event right away); a running job is flagged so its stream stops at
        the next window boundary and ends with ``cancelled`` instead of
        ``done``. Returns the job's new status; terminal jobs are left
        as-is. Raises KeyError for unknown ids."""
        job = self.jobs[job_id]
        if job.status == "queued":
            job.status = "cancelled"
            self.queued -= 1
            self.cancelled += 1
            job.events.put_nowait(
                {"event": "cancelled", "job": job.id, "was": "queued"}
            )
        elif job.status == "running":
            job.status = "cancelling"
            self.cancelled += 1
        return job.status

    async def results(self, job_id: str):
        """Async-iterate a job's event stream until its terminal event."""
        job = self.jobs[job_id]
        while True:
            event = await job.events.get()
            yield event
            if event["event"] in ("done", "error", "rejected", "cancelled"):
                return

    async def close(self) -> None:
        if self._worker is not None:
            await self._pending.put(None)
            await self._worker
            self._worker = None

    # -- worker side --------------------------------------------------------

    async def _run_loop(self) -> None:
        loop = asyncio.get_running_loop()
        while True:
            head = await self._pending.get()
            if head is None:
                return
            if head.status != "queued":  # cancelled while waiting
                continue
            batch = await self._gather_batch(head)
            if not batch:
                continue
            self.batches_run += 1
            for job in batch:
                job.status = "running"
                self.queued -= 1
            try:
                await loop.run_in_executor(None, self._run_batch, batch, loop)
            except Exception as err:  # surface, don't kill the worker
                for job in batch:
                    job.status = "error"
                    job.events.put_nowait(
                        {"event": "error", "job": job.id, "message": str(err)}
                    )
            else:
                for job in batch:
                    if job.status == "cancelling":
                        job.status = "cancelled"
                    else:
                        job.status = "done"
                        self.jobs_done += 1

    async def _gather_batch(self, head: SimJob) -> list[SimJob]:
        """Drain queued jobs that share ``head``'s signature (briefly
        waiting for stragglers); re-queue the rest in arrival order."""
        loop = asyncio.get_running_loop()
        batch, requeue = [head], []
        deadline = loop.time() + self.batch_wait
        while len(batch) < self.max_batch:
            timeout = deadline - loop.time()
            if timeout <= 0 and self._pending.empty():
                break
            try:
                nxt = await asyncio.wait_for(
                    self._pending.get(), max(timeout, 0.0)
                )
            except asyncio.TimeoutError:
                break
            if nxt is None:
                self._pending.put_nowait(None)  # preserve the shutdown signal
                break
            if nxt.status != "queued":  # cancelled while waiting
                continue
            if nxt.signature == head.signature:
                batch.append(nxt)
            else:
                requeue.append(nxt)
        for job in requeue:
            self._pending.put_nowait(job)
        return batch

    def _run_batch(self, batch: list[SimJob], loop) -> None:
        """Executor-thread body: build the ensemble (window callable from
        the signature cache), run it, stream each window bundle back."""
        specs = [job.spec for job in batch]
        window_fn = self.cache.get(batch[0].signature)
        ens = EnsembleSimulation(
            [(build_fields(s), build_particles(s)) for s in specs],
            pic_config(specs[0]),
            specs[0].sort.policy,
            specs=specs,
            window_fn=window_fn,
        )
        seen = [0] * len(batch)

        def post(job: SimJob, event: dict) -> None:
            loop.call_soon_threadsafe(job.events.put_nowait, event)

        def on_window(e: EnsembleSimulation, host: dict) -> None:
            for slot, job in enumerate(batch):
                if job.status == "cancelling":  # flagged: stop streaming
                    continue
                mb = member_bundle(host, slot)
                records = e.histories[slot][seen[slot]:]
                seen[slot] = len(e.histories[slot])
                post(job, {
                    "event": "window",
                    "job": job.id,
                    "step": int(e.host_step[slot]),
                    "n_done": int(mb["n_done"]),
                    "n_sorts": int(mb["n_sorts"]),
                    "halt_code": int(mb["halt_code"]),
                    "records": records,
                })

        ens.run(on_window=on_window)
        for slot, job in enumerate(batch):
            if job.status == "cancelling":
                post(job, {
                    "event": "cancelled",
                    "job": job.id,
                    "was": "running",
                    "step": int(ens.host_step[slot]),
                })
                continue
            post(job, {
                "event": "done",
                "job": job.id,
                "signature": job.signature,
                "batch_size": len(batch),
                "diagnostics": ens.diagnostics(slot),
                "history": ens.histories[slot],
            })


async def serve(service: SimService, host: str = "127.0.0.1", port: int = 8571):
    """JSON-lines TCP front end: each line in is ``{"spec": {...}}`` (event
    stream out, ending with a terminal event) or ``{"cancel": "job-N"}``
    (single ack line out)."""
    await service.start()

    async def handle(reader, writer):
        try:
            while line := await reader.readline():
                try:
                    request = json.loads(line)
                    if "cancel" in request:
                        status = service.cancel(request["cancel"])
                        writer.write(
                            (json.dumps({"event": "cancel",
                                         "job": request["cancel"],
                                         "status": status}) + "\n").encode()
                        )
                        await writer.drain()
                        continue
                    job_id = await service.submit(request["spec"])
                except Exception as err:
                    writer.write(
                        (json.dumps({"event": "error", "message": str(err)}) + "\n")
                        .encode()
                    )
                    await writer.drain()
                    continue
                async for event in service.results(job_id):
                    writer.write((json.dumps(event) + "\n").encode())
                    await writer.drain()
        finally:
            writer.close()

    return await asyncio.start_server(handle, host, port)


# -- smoke lane (CI) --------------------------------------------------------


async def _smoke(args) -> int:
    from repro.api.registry import scenario

    base = scenario(
        "uniform", grid=(args.grid,) * 3, ppc=2, steps=args.steps,
        window=args.window, diagnostics_every=args.window, backend="xla",
    )
    svc = SimService(max_batch=args.members, batch_wait=0.25)
    await svc.start()
    t0 = time.perf_counter()
    ids = [
        await svc.submit(base.to_json()) for _ in range(args.members)
    ]
    finals, windows = {}, {}
    for job_id in ids:
        windows[job_id] = 0
        async for event in svc.results(job_id):
            if event["event"] == "window":
                windows[job_id] += 1
            elif event["event"] == "error":
                print(f"FAIL: {job_id} errored: {event['message']}")
                return 1
            else:
                finals[job_id] = event
    elapsed = time.perf_counter() - t0
    await svc.close()

    ok = True
    for job_id in ids:
        done = finals[job_id]
        steps = done["diagnostics"]["step"]
        if steps != args.steps:
            print(f"FAIL: {job_id} ran {steps} steps, wanted {args.steps}")
            ok = False
        if windows[job_id] < 1:
            print(f"FAIL: {job_id} streamed no window events")
            ok = False
    sizes = {finals[j]["batch_size"] for j in ids}
    if sizes != {args.members}:
        print(f"FAIL: jobs ran in batches of {sorted(sizes)}, "
              f"wanted one batch of {args.members}")
        ok = False
    # Admission control + cancellation, deterministically: a bounded
    # service whose worker is never started, so queue state can't race.
    adm = SimService(max_batch=1, max_queue=1)
    j1 = await adm.submit(base.to_json())
    j2 = await adm.submit(base.to_json())  # over the bound -> rejected
    ev2 = [e async for e in adm.results(j2)]
    if [e["event"] for e in ev2] != ["rejected"]:
        print(f"FAIL: over-bound submit streamed {ev2}, wanted one rejected")
        ok = False
    status = adm.cancel(j1)
    ev1 = [e async for e in adm.results(j1)]
    if status != "cancelled" or [e["event"] for e in ev1] != ["cancelled"]:
        print(f"FAIL: queued cancel gave status={status}, events={ev1}")
        ok = False
    if (adm.queued, adm.rejected, adm.cancelled) != (0, 1, 1):
        print(f"FAIL: admission counters queued={adm.queued} "
              f"rejected={adm.rejected} cancelled={adm.cancelled}")
        ok = False

    print(
        f"sim_serve smoke: {len(ids)} jobs, batch={sorted(sizes)}, "
        f"{windows[ids[0]]} windows/job, cache={svc.cache.stats()}, "
        f"admission rejected={adm.rejected} cancelled={adm.cancelled}, "
        f"{elapsed:.2f}s -> {'OK' if ok else 'FAIL'}"
    )
    return 0 if ok else 1


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--smoke", action="store_true",
                        help="run the self-checking 2-member smoke lane and exit")
    parser.add_argument("--members", type=int, default=2,
                        help="smoke: jobs to submit (batched into one ensemble)")
    parser.add_argument("--grid", type=int, default=6,
                        help="smoke: cells per grid axis")
    parser.add_argument("--steps", type=int, default=8,
                        help="smoke: steps per job")
    parser.add_argument("--window", type=int, default=4,
                        help="smoke: window length")
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--port", type=int, default=8571)
    args = parser.parse_args(argv)
    enable_compile_cache()

    if args.smoke:
        return asyncio.run(_smoke(args))

    async def _serve_forever():
        svc = SimService()
        server = await serve(svc, args.host, args.port)
        addr = server.sockets[0].getsockname()
        print(f"sim_serve: listening on {addr[0]}:{addr[1]} (JSON lines)")
        async with server:
            await server.serve_forever()

    asyncio.run(_serve_forever())
    return 0


if __name__ == "__main__":
    sys.exit(main())
