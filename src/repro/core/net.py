"""RPC fabric for the in-process cluster.

Servers call each other through `Router.rpc(...)` — a direct Python call
wrapped with virtual-time accounting (destination NIC bandwidth + RTT, or the
loopback cost for a colocated client in the detached deployment, or zero for
the embedded deployment, §3.1).  Failure injection: dead destinations time
out; named injection points raise `SimCrash` inside server code to emulate
the black-dot crashes of Fig. 8.

Dispatch is *typed*: every remotely callable handler is registered with the
`@rpc_handler` decorator, which attaches an `RpcSpec` (wire name + declared
default payload sizes).  `Router.register` collects each server's handler
table once, and `Router.rpc` dispatches through it — an unregistered method
name is a programming error (`UnknownRpcError`), not a silent `getattr`.
The router also records per-method call counts, bytes, and virtual-time
latency globally (`Router.method_stats`), and a per-method call count into
the destination server's `stats` dict (`rpc.<method>.calls`).

Admission control (QoS) lives at this layer too, because the envelope is
the unit the fabric can police without understanding filesystem
semantics.  `Router.set_admission` installs a per-tenant GCRA token
bucket (`TenantQos`: ``rate_ops_s`` in *envelope* units — one fs-op is
several envelopes, ~4.7 for the mixed strict workload — plus ``burst``
and ``queue_depth``).  A conforming envelope passes untouched; an
over-rate one is *delayed* until its conforming time, up to
``queue_depth`` token intervals, beyond which it is *shed* as a typed
`AdmissionError` (EAGAIN, carrying ``retry_after_s``) without consuming
a token.  Untagged clients and the control-plane ``rpc_nodelist`` are
never policed.

The subtle part is *when* an envelope is charged.  An op queued behind a
backlog issues its trailing envelopes at post-queueing virtual times; if
the bucket charged those at dispatch time, the backlog itself would mint
refill credit (time passed → tokens accrued) and an overloaded tenant
would never shed.  Callers therefore pin each operation's charge time to
its open-loop *arrival* via `Router.note_arrival`, and `_admit` converts
the conforming-time wait into an incremental delay on top of whatever
straggle the envelope already carries.  Admission delays compose with
§5.2 dirty-page backpressure: the client diffs `Router.tenant_delay_s`
around staging and stalls only for the remainder of a ``bp_delay`` hint,
so the same virtual second is never charged twice.  Per-tenant
admitted/delayed/shed counters live in `Router.tenant_stats`.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import TYPE_CHECKING, Any, Callable

from .simclock import HardwareModel, SimClock
from .types import AdmissionError, FSError

if TYPE_CHECKING:  # pragma: no cover
    from .server import CacheServer


class SimTimeout(Exception):
    """RPC to a dead/partitioned node; charged `timeout_s` of virtual time."""


class SimCrash(Exception):
    """A server crashed at an injected point mid-operation."""

    def __init__(self, node: str, point: str) -> None:
        super().__init__(f"{node} crashed at {point}")
        self.node = node
        self.point = point


class UnknownRpcError(Exception):
    """Dispatch to a method name that no `@rpc_handler` registered."""


@dataclass(frozen=True)
class RpcSpec:
    """Declared wire contract of one RPC handler."""

    name: str                 # wire name (defaults to the function name)
    request_bytes: int = 256  # default request payload size when the caller
    reply_bytes: int = 256    # ... does not pass nbytes_out / nbytes_in


def rpc_handler(name: str | None = None, *, request_bytes: int = 256,
                reply_bytes: int = 256) -> Callable:
    """Mark a server-subsystem method as a remotely callable RPC handler.

    The handler signature is `m(start: float, **kwargs) -> (result, end)`.
    Registration happens when the owning server is `Router.register`-ed.
    """
    def deco(fn: Callable) -> Callable:
        fn.__rpc_spec__ = RpcSpec(name or fn.__name__,  # type: ignore[attr-defined]
                                  request_bytes, reply_bytes)
        return fn
    return deco


def collect_handlers(*objs: Any) -> dict[str, tuple[Callable, RpcSpec]]:
    """Scan objects for `@rpc_handler`-decorated methods -> dispatch table."""
    table: dict[str, tuple[Callable, RpcSpec]] = {}
    for obj in objs:
        for attr in dir(type(obj)):
            fn = getattr(type(obj), attr, None)
            spec = getattr(fn, "__rpc_spec__", None)
            if spec is not None:
                if spec.name in table:  # pragma: no cover
                    raise AssertionError(f"duplicate RPC handler {spec.name}")
                table[spec.name] = (getattr(obj, attr), spec)
    return table


@dataclass(frozen=True)
class TenantQos:
    """Per-tenant admission parameters: a token bucket plus a bounded queue.

    ``rate_ops_s`` is the sustained admitted envelope rate (token refill);
    ``burst`` is the bucket capacity — envelopes admitted back-to-back after
    an idle period; ``queue_depth`` is how many envelopes' worth of backlog
    the fabric will *delay* rather than shed, so the maximum admission delay
    is ``queue_depth / rate_ops_s``.  One wire envelope costs one token
    (a batch counts once, same as `Router.rpc_count`)."""

    rate_ops_s: float
    burst: int = 8
    queue_depth: int = 32


class AdmissionControl:
    """Virtual-time token buckets (GCRA form), one per policed tenant.

    The bucket is kept as a theoretical-arrival-time (`tat`) per tenant:
    an envelope arriving at ``now`` owes ``wait = max(0, tat - tol - now)``
    where ``tol = (burst - 1) / rate`` is the idle credit.  ``wait`` within
    the bounded queue is served as an admission *delay* (the envelope
    dispatches late); beyond it the envelope is *shed* without consuming a
    token.  Exact at simclock boundaries: after a drained burst the next
    token is available precisely ``1 / rate`` later.  Tenants without a
    policy entry are unpoliced.

    The bucket must be driven by a per-tenant *monotone* clock — the time
    the tenant's operation **arrived** at the fabric, not the time each
    envelope happens to dispatch.  Envelope dispatch times include the
    queueing delay of earlier envelopes (and any admission delay the fabric
    itself added), so charging them would let an over-rate tenant mint
    refill credit from its own backlog and never accumulate debt.  Callers
    with naturally monotone send times (closed-loop clients) just pass
    those; the open-loop runner pins the charge time for all of an op's
    envelopes to the op's scheduled arrival via `Router.note_arrival`."""

    def __init__(self, policy: dict[str, TenantQos]) -> None:
        self.policy = dict(policy)
        self._tat: dict[str, float] = {}

    def decide(self, tenant: str, now: float) -> tuple[str, float]:
        """Returns ("admit", 0) | ("delay", wait) | ("shed", retry_after);
        `wait` is relative to `now`, the envelope's charge time."""
        qos = self.policy.get(tenant)
        if qos is None:
            return "admit", 0.0
        inc = 1.0 / qos.rate_ops_s
        tol = (max(1, qos.burst) - 1) * inc
        tat = max(self._tat.get(tenant, 0.0), now)
        wait = tat - tol - now
        # epsilon on both comparisons: tat accumulates `inc` per envelope,
        # so at an exact refill boundary (now == k / rate) float residue
        # would otherwise turn a conforming envelope into a spurious delay
        if wait <= 1e-12:
            self._tat[tenant] = tat + inc
            return "admit", 0.0
        if wait > qos.queue_depth * inc + 1e-12:
            return "shed", wait          # no token consumed, tat unchanged
        self._tat[tenant] = tat + inc
        return "delay", wait


class Router:
    def __init__(self, clock: SimClock, hw: HardwareModel,
                 timeout_s: float = 1.0) -> None:
        self.clock = clock
        self.hw = hw
        self.timeout_s = timeout_s
        self.servers: dict[str, "CacheServer"] = {}
        # node_id -> {method name -> (bound handler, spec)}
        self.handlers: dict[str, dict[str, tuple[Callable, RpcSpec]]] = {}
        self.partitioned: set[str] = set()
        # stats
        self.rpc_count = 0          # wire envelopes (a batch counts once)
        self.rpc_bytes = 0
        self.batch_envelopes = 0    # envelopes that carried > 1 sub-call
        self.batched_subcalls = 0   # sub-calls delivered inside batches
        # per-method: calls / bytes / vtime (summed reply latency) /
        # timeouts (unreachable dst) / errors (handler raised)
        self.method_stats: dict[str, dict[str, float]] = {}
        self._calls_keys: dict[str, str] = {}
        # per-tenant QoS admission at the fabric edge (None = everything
        # admitted); tenant_stats: admitted / delayed / shed / delay_s
        self.admission: AdmissionControl | None = None
        self.tenant_stats: dict[str, dict[str, float]] = {}
        # open-loop arrival stamps: tenant -> charge time for its envelopes
        # (see AdmissionControl docstring); absent = charge at dispatch time
        self.tenant_clock: dict[str, float] = {}

    def register(self, server: "CacheServer") -> None:
        self.servers[server.node_id] = server
        self.handlers[server.node_id] = server.rpc_handlers()

    def unregister(self, node_id: str) -> None:
        self.servers.pop(node_id, None)
        self.handlers.pop(node_id, None)

    def reachable(self, node_id: str) -> bool:
        s = self.servers.get(node_id)
        return s is not None and s.alive and node_id not in self.partitioned

    def registered_methods(self, node_id: str) -> list[str]:
        return sorted(self.handlers.get(node_id, {}))

    # ---- timing ----------------------------------------------------------------
    def xfer(self, src: str | None, dst: str, nbytes: int, start: float,
             embedded_local: bool = False) -> float:
        """Time for a one-way transfer src->dst.  src None = external client."""
        if src == dst:
            if embedded_local:
                return start  # embedded deployment: same process, no hop
            # detached deployment, same node: loopback
            return start + self.hw.loopback_rtt_s / 2 + nbytes / self.hw.loopback_bps
        dst_srv = self.servers.get(dst)
        nic = dst_srv.nic if dst_srv is not None else None
        t = start + self.hw.net_rtt_s / 2
        if nic is not None:
            return nic.acquire(t, nbytes)
        return t + nbytes / self.hw.nic_bps

    # ---- per-tenant QoS admission ----------------------------------------------
    def set_admission(self, policy: dict[str, TenantQos] | None) -> None:
        """Install (or clear, with None/{}) per-tenant admission control.
        The policy applies to tenant-tagged envelopes only; untagged calls
        (server-to-server traffic, the operator, control-plane pulls) are
        never policed."""
        self.admission = AdmissionControl(policy) if policy else None

    def _tstat(self, tenant: str) -> dict[str, float]:
        st = self.tenant_stats.get(tenant)
        if st is None:
            st = {"admitted": 0, "delayed": 0, "shed": 0, "delay_s": 0.0}
            self.tenant_stats[tenant] = st
        return st

    def tenant_delay_s(self, tenant: str | None) -> float:
        """Cumulative admission delay charged to `tenant` so far.  Clients
        diff this around an operation to compose server backpressure hints
        with admission delays instead of double-counting the stall."""
        if tenant is None:
            return 0.0
        st = self.tenant_stats.get(tenant)
        return st["delay_s"] if st is not None else 0.0

    def note_arrival(self, tenant: str, t: float) -> None:
        """Pin the admission charge time for `tenant`'s next envelopes to
        `t` — an open-loop driver calls this with each op's scheduled
        arrival, so all of the op's envelopes are charged as one burst at
        arrival instead of at their (queueing-inflated) dispatch times."""
        self.tenant_clock[tenant] = t

    def _admit(self, tenant: str | None, method: str, start: float) -> float:
        """Apply admission control to one envelope; returns the (possibly
        delayed) dispatch time, or raises `AdmissionError` on shed."""
        if tenant is None or self.admission is None:
            return start
        charge = self.tenant_clock.get(tenant, start)
        verdict, wait = self.admission.decide(tenant, charge)
        st = self._tstat(tenant)
        if verdict == "shed":
            st["shed"] += 1
            raise AdmissionError(tenant, method, wait)
        st["admitted"] += 1
        if verdict == "delay":
            # the envelope may dispatch once its conforming time (relative
            # to the charge clock) has passed; service straggle that already
            # pushed `start` beyond it absorbs the admission delay for free
            extra = max(0.0, charge + wait - start)
            if extra > 0.0:
                st["delayed"] += 1
                st["delay_s"] += extra
            return start + extra
        return start

    def _mstat(self, method: str) -> dict[str, float]:
        st = self.method_stats.get(method)
        if st is None:
            st = {"calls": 0, "bytes": 0, "vtime": 0.0, "timeouts": 0,
                  "errors": 0}
            self.method_stats[method] = st
        return st

    def _calls_key(self, method: str) -> str:
        key = self._calls_keys.get(method)
        if key is None:
            key = self._calls_keys[method] = f"rpc.{method}.calls"
        return key

    def rpc(self, src: str | None, dst: str, method: str, start: float,
            nbytes_out: int | None = None, nbytes_in: int | None = None,
            nbytes_extra: int = 0, embedded_local: bool = False,
            tenant: str | None = None, **kwargs: Any) -> tuple[Any, float]:
        """Invoke registered handler `method` on server `dst`.

        The handler signature is `m(start: float, **kwargs) -> (result,
        end_time)`.  Returns the result and the time the reply lands back at
        the caller.  Payload sizes default to the handler's declared
        `RpcSpec` when not passed explicitly.  `nbytes_extra` declares
        payload bytes the handler moves on *other* resources on behalf of
        this call (e.g. a chunk-owner's MPU part upload straight to COS):
        they count toward the method's byte accounting so `rpc_stats()` is
        truthful about where the data goes, but are not charged to the
        src->dst NIC transfer, which only carries the control message.
        A `tenant` tag subjects the envelope to the installed admission
        policy: it may dispatch late (queued) or raise `AdmissionError`
        (shed) before any transfer is charged."""
        # a bad method name is a programming error even when the node is
        # down — surface it before (and without) any timeout accounting
        node_handlers = self.handlers.get(dst)
        if node_handlers is not None and method not in node_handlers:
            raise UnknownRpcError(
                f"no RPC handler {method!r} registered on {dst}; "
                f"known: {self.registered_methods(dst)}")
        start = self._admit(tenant, method, start)
        if not self.reachable(dst):
            self._mstat(method)["timeouts"] += 1
            raise SimTimeout(f"rpc {method} to {dst}: timeout "
                             f"(+{self.timeout_s}s at t={start:.6f})")
        fn, spec = node_handlers[method]
        n_out = spec.request_bytes if nbytes_out is None else nbytes_out
        n_in = spec.reply_bytes if nbytes_in is None else nbytes_in
        arrive = self.xfer(src, dst, n_out, start, embedded_local)
        server = self.servers[dst]
        try:
            result, end = fn(start=arrive, **kwargs)
        except BaseException:
            # failed dispatch (FSError / injected crash): keep the completed-
            # call counters consistent, account the failure separately
            self._mstat(method)["errors"] += 1
            raise
        back = self.xfer(dst, src, n_in, end, embedded_local) \
            if src is not None else self.xfer(dst, dst, n_in, end, True)
        latency = back - start
        # all call counters (legacy globals + per-method + per-server) count
        # *completed* dispatches; failures land in timeouts/errors above
        n_total = n_out + n_in + max(0, nbytes_extra)
        self.rpc_count += 1
        self.rpc_bytes += n_total
        mstat = self._mstat(method)
        mstat["calls"] += 1
        mstat["bytes"] += n_total
        mstat["vtime"] += latency
        k_calls = self._calls_key(method)
        sstats = server.stats
        sstats[k_calls] = sstats.get(k_calls, 0) + 1
        return result, back

    def rpc_batch(self, src: str | None, dst: str, calls: list[dict],
                  start: float, embedded_local: bool = False,
                  tenant: str | None = None
                  ) -> tuple[list[tuple[str, Any, float]], float]:
        """Same-destination coalescing: one wire envelope carrying N typed
        sub-calls.  Each element of `calls` is
        ``{"method": str, "kwargs": dict, "nbytes_out"?, "nbytes_in"?,
        "nbytes_extra"?}``.

        All sub-calls dispatch at the envelope's arrival time (server-side
        fan-out; shared hardware resources still serialize in virtual time
        through their lanes) and the reply lands after the *latest* sub-call
        completes.  Returns ``([("ok", result, end) | ("err", exc, end)],
        reply_time)`` — an `FSError` in one sub-call is reported in its slot
        without failing the others, exactly like N independent RPCs would
        behave.  Accounting: one envelope in `rpc_count`, but per-method
        calls/bytes/vtime are still credited per sub-call so `rpc_stats()`
        keeps full method visibility (plus a per-method `batched` counter)."""
        node_handlers = self.handlers.get(dst)
        if node_handlers is not None:
            for c in calls:
                if c["method"] not in node_handlers:
                    raise UnknownRpcError(
                        f"no RPC handler {c['method']!r} registered on {dst}; "
                        f"known: {self.registered_methods(dst)}")
        # one envelope = one token, same unit as rpc_count
        start = self._admit(tenant, f"batch[{len(calls)}]", start)
        if not self.reachable(dst):
            for c in calls:
                self._mstat(c["method"])["timeouts"] += 1
            raise SimTimeout(f"rpc_batch x{len(calls)} to {dst}: timeout "
                             f"(+{self.timeout_s}s at t={start:.6f})")
        sized = []
        for c in calls:
            fn, spec = node_handlers[c["method"]]
            n_out = c.get("nbytes_out")
            n_in = c.get("nbytes_in")
            sized.append((c, fn,
                          spec.request_bytes if n_out is None else n_out,
                          spec.reply_bytes if n_in is None else n_in))
        # one envelope: summed payloads + a small per-sub-call frame header
        total_out = sum(n for _, _, n, _ in sized) + 16 * len(sized)
        total_in = sum(n for _, _, _, n in sized) + 16 * len(sized)
        arrive = self.xfer(src, dst, total_out, start, embedded_local)
        server = self.servers[dst]
        results: list[tuple[str, Any, float]] = []
        ends = [arrive]
        for c, fn, n_out, n_in in sized:
            try:
                result, end = fn(start=arrive, **c["kwargs"])
                results.append(("ok", result, end))
                ends.append(end)
            except FSError as e:
                self._mstat(c["method"])["errors"] += 1
                results.append(("err", e, arrive))
        back = self.xfer(dst, src, total_in, max(ends), embedded_local) \
            if src is not None else self.xfer(dst, dst, total_in, max(ends),
                                              True)
        latency = back - start
        self.rpc_count += 1
        if len(calls) > 1:
            self.batch_envelopes += 1
            self.batched_subcalls += len(calls)
        sstats = server.stats
        for (c, fn, n_out, n_in), (status, _r, _e) in zip(sized, results):
            if status != "ok":
                continue
            n_total = n_out + n_in + max(0, c.get("nbytes_extra", 0))
            self.rpc_bytes += n_total
            mstat = self._mstat(c["method"])
            mstat["calls"] += 1
            mstat["bytes"] += n_total
            mstat["vtime"] += latency
            mstat["batched"] = mstat.get("batched", 0) + (len(calls) > 1)
            k_calls = self._calls_key(c["method"])
            sstats[k_calls] = sstats.get(k_calls, 0) + 1
        return results, back

    def charge_timeout(self, start: float) -> float:
        return start + self.timeout_s
