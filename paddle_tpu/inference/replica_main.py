"""One serving replica as a REAL process: ``python -m
paddle_tpu.inference.replica_main --name replica-0 --port 8471``.

This is the subprocess body the :class:`~paddle_tpu.inference.
fleet_supervisor.ReplicaSupervisor` spawns — the piece that turns the
in-process fleet of PRs 10–15 into a fleet that can actually die.  It
builds one engine (``--model tiny``: the seeded tiny-Llama ``LLMEngine``,
token-identical across replicas; ``--model stub``: a compile-free stub
engine for supervisor-level tests), wraps it in a ``ReplicaServer`` on
the ASSIGNED ``--port`` (the supervisor pins the address so restarts
rebind it), and serves until SIGTERM.

Signal/deadline contract (README §Serving, "Multi-process fleet"):

- SIGTERM => drain bounded by ``--drain-deadline`` (requests still in
  flight past it fail with ``DeadlineExceededError`` — never silently
  dropped), then clean exit 0.  The supervisor escalates to SIGKILL only
  after its own grace deadline expires.
- Readiness is ``/healthz`` 200 on the assigned port — the supervisor
  gates rotation entry on it.

Fault seams (testing/faults.py ``ProcFaults``): the spec arrives via the
``PADDLE_TPU_PROC_FAULTS`` env var (armed per-incarnation by the
supervisor) or at runtime through ``POST /faultz`` (only when spawned
with ``--allow-faultz``); ``/admitz`` and ``/pollz`` are wrapped with
the call-counted kill seams, and ``wedge_drain`` turns the SIGTERM drain
into a wedge so escalation paths are testable.  All of it is inert in
production spawns: no env var, no ``--allow-faultz``, no overhead beyond
two counter increments.
"""
from __future__ import annotations

import argparse
import json
import signal
import sys
import threading
import time

import numpy as np

from ..testing import faults as _faults


class _StubEngine:
    """Compile-free engine stand-in for supervisor-level chaos tests.

    Implements exactly the surface ``ReplicaServer`` and the drain
    contract need — telemetry (with the ``admission`` healthcheck the
    router's drain detection reads), ``submit`` resolving a
    deterministic token list immediately, ``drain``/``resume``/
    ``start``/``stop``.  Tokens are a pure function of the prompt, so
    zero-double-delivery and exactly-once assertions hold across
    replicas and restarts without ever compiling a model.
    """

    def __init__(self, port):
        from ..observability.exporter import TelemetryServer

        self._draining = False
        self.telemetry = TelemetryServer(port=port)
        self.telemetry.register_healthcheck("pump", lambda: (True, "stub"))
        self.telemetry.register_healthcheck("admission",
                                            self._check_admission)
        self.telemetry.start()

    def _check_admission(self):
        if self._draining:
            return False, "draining"
        return True, "accepting"

    @staticmethod
    def tokens_for(prompt_ids, n):
        """The deterministic oracle tests compare deliveries against."""
        base = int(np.asarray(prompt_ids, np.int64).sum())
        return [(base + 31 * i) % 50257 for i in range(int(n))]

    def submit(self, prompt_ids, max_new_tokens=32, on_admit=None,
               **kwargs):
        from .llm_server import ServerOverloadedError
        from concurrent.futures import Future

        if self._draining:
            raise ServerOverloadedError("draining: shedding new requests")
        fut = Future()
        if on_admit is not None:
            on_admit()
        fut.set_result(self.tokens_for(prompt_ids, max_new_tokens))
        return fut

    def stats(self):
        return {"draining": self._draining, "queue_depth": 0}

    def drain(self, timeout=None, deadline_s=None):
        self._draining = True
        return True

    def resume(self):
        self._draining = False
        return self

    def start(self):
        return self

    def stop(self):
        self.telemetry.stop()


def _build_engine(args):
    """``--model tiny``: the fleetserve tiny-Llama engine (identical
    seeded weights on every replica => token parity across the fleet);
    ``--model stub``: no model at all."""
    if args.model == "stub":
        return _StubEngine(args.port)
    import paddle_tpu as paddle
    from .llm_server import LLMEngine
    from ..core.device import enable_compile_cache
    from ..models import LlamaConfig, LlamaForCausalLM

    # every (re)start of a replica compiles the same programs: load them
    enable_compile_cache()
    paddle.seed(args.seed)
    cfg = LlamaConfig.tiny(tensor_parallel=False, use_flash_attention=False,
                           max_position_embeddings=max(256,
                                                       args.max_seq_len))
    model = LlamaForCausalLM(cfg)
    model.eval()
    eng = LLMEngine(model, max_batch_slots=args.slots,
                    max_seq_len=args.max_seq_len, kv_layout="paged",
                    page_size=args.page_size, prefill_chunk=args.page_size,
                    metrics_port=args.port)
    eng.start()
    return eng


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--name", default="replica-0")
    ap.add_argument("--port", type=int, required=True,
                    help="assigned telemetry+data port (pinned by the "
                         "supervisor across restarts)")
    ap.add_argument("--model", choices=("tiny", "stub"), default="tiny")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--max-seq-len", type=int, default=128)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--drain-deadline", type=float, default=10.0,
                    help="SIGTERM drain bound (seconds); in-flight work "
                         "past it fails with DeadlineExceededError")
    ap.add_argument("--allow-faultz", action="store_true",
                    help="expose POST /faultz (runtime fault arming — "
                         "test harness only)")
    args = ap.parse_args(argv)

    faults = _faults.load_proc_faults()
    if faults.exit_at_start:
        return 3  # injected crash-at-start (restart-storm fodder)
    if faults.slow_start_s > 0:
        time.sleep(faults.slow_start_s)  # readiness delayed past the gate

    from .router import ReplicaServer

    engine = _build_engine(args)
    server = ReplicaServer(engine, name=args.name)
    tel = engine.telemetry

    # fault seams: wrap the wire endpoints ReplicaServer just registered
    # (re-registration replaces; the originals are its bound methods)
    def admitz(query, body):
        faults.on_admit()  # may SIGKILL this process before the reply
        return server._admitz(query, body)

    def pollz(query):
        faults.on_poll()
        return server._pollz(query)

    tel.register_post_endpoint("/admitz", admitz)
    tel.register_json_endpoint("/pollz", pollz)

    if args.allow_faultz:
        def faultz(query, body):
            try:
                spec = json.loads(body or b"{}")
            except ValueError as e:
                return 400, {"error": f"bad fault spec: {e!r}"}
            # counters let a harness arm "the Nth call from NOW"
            # deterministically: read, add, re-POST the absolute index
            return 200, {"armed": faults.arm(spec),
                         "admits": faults.admits, "polls": faults.polls}

        tel.register_post_endpoint("/faultz", faultz)

    # /drainz: supervisor-driven bounded drain (scale-down reaps call it
    # before SIGTERM so in-flight work completes while the process is
    # still in the rotation's past)
    def drainz(query, body):
        try:
            doc = json.loads(body or b"{}")
            deadline_s = float(doc.get("deadline_s", args.drain_deadline))
        except (ValueError, TypeError) as e:
            return 400, {"error": f"bad drain request: {e!r}"}
        ok = engine.drain(deadline_s=deadline_s)
        return 200, {"drained": bool(ok)}

    tel.register_post_endpoint("/drainz", drainz)

    stop_ev = threading.Event()
    signal.signal(signal.SIGTERM, lambda *a: stop_ev.set())
    signal.signal(signal.SIGINT, lambda *a: stop_ev.set())
    print(f"replica {args.name} serving on {tel.host}:{tel.port} "
          f"(model={args.model})", flush=True)
    stop_ev.wait()

    if faults.wedge_drain:
        # injected crash-during-drain: never finish shutting down — the
        # supervisor must SIGKILL us on its escalation deadline
        while True:
            time.sleep(60)
    engine.drain(deadline_s=args.drain_deadline)
    engine.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
