"""Live mode: the scenario harness on real time and real work.

The DES benches model an action as a duration; live mode *runs* it — a
real payload (JAX kernel work from :mod:`repro.kernels.ops`) on a
thread-pool worker, against :class:`~repro.core.simulator.RealClock`.
On an accelerator the pools map round-robin onto the real devices and
the kernel is compiled; on the CPU the fleet is emulated XLA host
devices (``--xla_force_host_platform_device_count``, so CI exercises a
multi-device fleet on plain CPU) and the kernel runs in interpret mode.

The control plane is unchanged: :class:`LiveOrchestrator` overrides
exactly one method (``_schedule_completion`` — the seam
:class:`~repro.core.orchestrator.Orchestrator` exposes for this) so a
launch dispatches the payload instead of arming a virtual timer, and
completion happens when the work actually returns.  Everything else —
queues, scheduler, managers, fairness, telemetry — is the same code the
sim runs, which is what makes the **differential replay rail** honest:
the same compiled :class:`~repro.core.scenarios.CompiledScenario` drives
both modes, and the live run's launch trace must be *structurally*
equivalent to the sim's (same per-pool launch order; real timing is
reported separately, never compared — see
:func:`repro.core.scenarios.structural_trace`).
"""

from __future__ import annotations

import heapq
import itertools
import os
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, List, Optional

from repro.core.action import Action, ActionState
from repro.core.orchestrator import ActionError, Orchestrator
from repro.core.scenarios import ActionTemplate, CompiledScenario
from repro.core.simulator import RealClock, _Event


class LiveModeError(RuntimeError):
    """Live-mode environment failure (devices unavailable, jax imported
    too early to emulate the requested fleet, ...)."""


def live_devices(n: int) -> list:
    """The devices a live run with ``n`` pools maps its pools onto.

    On an accelerator: every real device (pools map onto them
    round-robin; the host-device flag means nothing there).  On the CPU:
    ``n`` emulated XLA host devices, setting
    ``--xla_force_host_platform_device_count`` if jax has not been
    imported yet.  The bench CLI calls this before any jax import; a
    caller who imported jax first (fixing the CPU device count at 1)
    gets a typed error, not a silently single-device run."""
    import sys

    flag = f"--xla_force_host_platform_device_count={n}"
    if "jax" not in sys.modules:
        # only the CPU backend reads this flag, so it is inert when an
        # accelerator is found
        flags = os.environ.get("XLA_FLAGS", "")
        if "--xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (flags + " " + flag).strip()
    import jax

    devices = jax.devices()
    if devices[0].platform != "cpu":
        return list(devices)
    if len(devices) < n:
        raise LiveModeError(
            f"live mode needs {n} host devices, jax sees {len(devices)} "
            f"(set XLA_FLAGS={flag} before the first jax import)"
        )
    return list(devices[:n])


class LiveEventLoop:
    """The event loop on wall time.

    Same surface as :class:`~repro.core.simulator.EventLoop` (``call_at``
    / ``call_after`` / ``cancel`` / ``run`` / ``pending`` / ``clock``),
    but timers fire at real instants and worker threads hand completions
    back with :meth:`post` (callbacks always execute on the loop thread,
    so the orchestrator stays single-threaded exactly as in sim mode).
    ``run`` drains until there are no timers, no posted callbacks, and
    no retained in-flight work."""

    def __init__(self) -> None:
        self.clock = RealClock()
        self._heap: List[_Event] = []
        self._seq = itertools.count()
        self._posted: deque = deque()
        self._cond = threading.Condition()
        self._inflight = 0

    # -- scheduling (loop thread) --------------------------------------
    def call_at(self, when: float, callback: Callable[[], None]) -> _Event:
        # real time moved on while the caller computed `when`; a
        # slightly-past deadline just means "as soon as possible"
        ev = _Event(when=when, seq=next(self._seq), callback=callback)
        with self._cond:
            heapq.heappush(self._heap, ev)
            self._cond.notify()
        return ev

    def call_after(self, delay: float, callback: Callable[[], None]) -> _Event:
        return self.call_at(self.clock.now() + max(0.0, delay), callback)

    def cancel(self, ev: _Event) -> None:
        ev.cancelled = True

    def pending(self) -> int:
        with self._cond:
            return sum(1 for e in self._heap if not e.cancelled)

    # -- worker-thread handoff -----------------------------------------
    def retain(self) -> None:
        """Mark one unit of off-loop work in flight (keeps ``run`` from
        exiting while a payload is still executing)."""
        with self._cond:
            self._inflight += 1

    def release(self) -> None:
        with self._cond:
            self._inflight -= 1
            self._cond.notify()

    def post(self, callback: Callable[[], None]) -> None:
        """Enqueue a callback from any thread; it runs on the loop
        thread ahead of timer events."""
        with self._cond:
            self._posted.append(callback)
            self._cond.notify()

    # -- the loop -------------------------------------------------------
    def run(self, until: Optional[float] = None,
            max_events: int = 1_000_000) -> float:
        n = 0
        while True:
            cb: Optional[Callable[[], None]] = None
            with self._cond:
                while True:
                    now = self.clock.now()
                    if until is not None and now >= until:
                        return now
                    if self._posted:
                        cb = self._posted.popleft()
                        break
                    while self._heap and self._heap[0].cancelled:
                        heapq.heappop(self._heap)
                    if self._heap and self._heap[0].when <= now:
                        cb = heapq.heappop(self._heap).callback
                        break
                    if not self._heap and self._inflight == 0:
                        return now
                    deadline = self._heap[0].when if self._heap else None
                    timeout = (None if deadline is None
                               else max(0.0, deadline - now))
                    if until is not None:
                        wall = max(0.0, until - now)
                        timeout = wall if timeout is None else min(timeout, wall)
                    self._cond.wait(timeout)
            cb()
            n += 1
            if n >= max_events:
                raise RuntimeError(f"event budget exceeded ({max_events})")


class LiveOrchestrator(Orchestrator):
    """The orchestrator on real work: launches dispatch the action's
    payload (``action.fn``, or a real sleep of the modeled duration) to
    a thread pool, and completion fires when the payload returns.  All
    other lifecycle paths — withdraw, deadline/retry, telemetry — are
    the inherited sim-mode code."""

    def __init__(self, managers, *, loop: Optional[LiveEventLoop] = None,
                 max_workers: int = 8, **kwargs) -> None:
        super().__init__(managers, loop=loop or LiveEventLoop(), **kwargs)
        self._pool = ThreadPoolExecutor(
            max_workers=max_workers, thread_name_prefix="live-action")
        #: (action, exception) for every payload that raised; each such
        #: action ends FAILED, and :func:`run_live_scenario` fails the run
        self.payload_errors: List[tuple] = []
        #: pool -> the devices its kernel payloads' outputs landed on
        self.payload_devices: Dict[str, set] = {}

    def _schedule_completion(self, action: Action, duration: float,
                             overhead: float) -> None:
        # the modeled finish is only an estimate; the real one is
        # stamped when the payload returns
        action.finish_time = self.now + overhead + duration
        loop = self.loop
        loop.retain()

        def work() -> None:
            action.start_ns = time.perf_counter_ns()
            err: Optional[Exception] = None
            try:
                if action.fn is not None:
                    action.fn()
                else:
                    time.sleep(duration)
            except Exception as e:  # noqa: BLE001 - handed to the loop
                err = e
            finally:
                action.end_ns = time.perf_counter_ns()
                real_s = (action.end_ns - action.start_ns) / 1e9
                loop.post(lambda: self._on_live_done(action, real_s, err))

        self._pool.submit(work)

    def _on_live_done(self, action: Action, real_s: float,
                      err: Optional[Exception] = None) -> None:
        try:
            if action.uid not in self._executing:
                return  # withdrawn (timeout/cancel) while the work ran
            if err is None:
                action.finish_time = self.now
                self._complete(action, real_s)
                return
            self.payload_errors.append((action, err))
            released = self._withdraw(action)
            self._finalize_failure(action, ActionState.FAILED, ActionError(
                action, f"payload raised {type(err).__name__}: {err}"))
            self._dirty_rtypes(released)
            self._request_round()
        finally:
            self.loop.release()

    def close(self) -> None:
        self._pool.shutdown(wait=True)
        super().close()


# ---------------------------------------------------------------------------
# Kernel payloads: real JAX work per device
# ---------------------------------------------------------------------------


#: The payload's kernel operand shape; warm-up compiles exactly this.
PAYLOAD_SHAPE = (64, 64)


def _kernel_operands(dev):
    import jax
    import jax.numpy as jnp

    rows, cols = PAYLOAD_SHAPE
    x = jax.device_put(jnp.ones((rows, cols), jnp.float32), dev)
    w = jax.device_put(jnp.ones((cols,), jnp.float32), dev)
    # Pallas TPU kernels compile only for the TPU; elsewhere they run in
    # the interpreter
    return x, w, dev.platform != "tpu"


def kernel_payload_factory(
    devices: list, pool_device: Dict[str, int], placements: Dict[str, set],
) -> Callable[[ActionTemplate], Callable[[], None]]:
    """Payloads that run a real Pallas kernel (``rmsnorm_op``) on the
    template's pool's device, one blocking call after another, until
    the template's (time-scaled) duration has elapsed.  Pools map onto
    ``devices`` round-robin.  Call :func:`warm_devices` first: the first
    call per device pays that device's compile, which would otherwise
    distort the run.  ``placements`` collects the devices each pool's
    output landed on."""
    import jax

    from repro.kernels.ops import rmsnorm_op

    def factory(template: ActionTemplate) -> Callable[[], None]:
        dev = devices[pool_device.get(template.rtype, 0) % len(devices)]
        target_s = template.base_duration

        def fn() -> None:
            x, w, interpret = _kernel_operands(dev)
            t0 = time.monotonic()
            while True:
                out = jax.block_until_ready(rmsnorm_op(x, w, interpret=interpret))
                if time.monotonic() - t0 >= target_s:
                    break
            placements.setdefault(template.rtype, set()).update(out.devices())

        return fn

    return factory


def warm_devices(devices: list) -> None:
    """One payload-shaped kernel call per device before the timed run
    (per-device jit specialization: each device's first call compiles)."""
    import jax

    from repro.kernels.ops import rmsnorm_op

    for dev in devices:
        x, w, interpret = _kernel_operands(dev)
        jax.block_until_ready(rmsnorm_op(x, w, interpret=interpret))


# ---------------------------------------------------------------------------
# The live runner (what the bench + CI smoke call)
# ---------------------------------------------------------------------------


def run_live_scenario(
    compiled: CompiledScenario,
    *,
    devices: Optional[list] = None,
    max_workers: Optional[int] = None,
    wall_limit_s: float = 300.0,
    use_kernels: bool = True,
):
    """Run a compiled scenario in live mode; returns the orchestrator
    (telemetry carries the real-time records; ``payload_devices`` maps
    each pool to the devices its kernel output landed on).
    ``use_kernels=False`` substitutes real sleeps for kernel work (same
    structural trace, no jax dependency).  A payload that raises fails
    its action, and then the run: :class:`LiveModeError`."""
    from repro.core.scenarios import build_fair_share, build_managers, \
        install_scenario
    from repro.core.scheduler import ElasticScheduler

    spec = compiled.spec
    loop = LiveEventLoop()
    managers = build_managers(spec, loop)
    orch = LiveOrchestrator(
        managers,
        loop=loop,
        policy=ElasticScheduler(),
        fair_share=build_fair_share(spec),
        incremental=True,
        max_workers=max_workers or max(4, 2 * len(spec.pools)),
    )
    payload = None
    if use_kernels:
        devs = devices or live_devices(len(spec.pools))
        warm_devices(devs)
        pool_device = {p.name: i for i, p in enumerate(spec.pools)}
        payload = kernel_payload_factory(devs, pool_device, orch.payload_devices)
    install_scenario(compiled, orch, payload=payload)
    orch.run(until=wall_limit_s)
    orch.close()
    if orch.payload_errors:
        action, err = orch.payload_errors[0]
        raise LiveModeError(
            f"{len(orch.payload_errors)} live payload(s) raised; first: "
            f"{action.name} on {action.trajectory_id}: {err!r}"
        ) from err
    return orch
