"""NTP-style clock-offset estimation over the host object plane
(ISSUE 17): the honesty layer under cross-rank timeline merges.

Every trace event stamps ``t`` from the local ``time.time()`` — two
processes' epochs can disagree by milliseconds (more between hosts
without a disciplined clock), which is larger than the handoff latencies
the journey merge wants to display. The classic two-way exchange bounds
it without any new transport: the client stamps ``t0``, the server
answers with its own clock ``t_srv``, the client stamps ``t1``, and

    offset_sample = t_srv - (t0 + t1) / 2        (server - client)

is exact when the path is symmetric and wrong by at most half the
round trip when it is not. Over ``n`` exchanges the estimate is the
MEDIAN sample (robust to a GC pause or a retransmit polluting one
exchange) and the uncertainty is ``min(rtt) / 2`` — the tightest
half-RTT seen, the standard NTP error bound. The result is emitted as
one ``clock_sync`` trace event, so merged timelines shift honestly
AND carry their error bar (``journey.clock_offsets`` consumes it; a
merge that silently trusted raw epochs would manufacture causality).

Transport contract: anything with ``send_obj(obj, dest)`` /
``recv_obj(source)`` — ``TcpHostComm`` across processes, the
in-process ``LoopbackHub`` endpoints in tests and the dryrun (where
``recv_obj`` raises instead of blocking: pass ``pump`` to run the
server's half between the client's send and recv). The reference
framework leaned on MPI's globally synchronized launch and never
needed this; a host-plane serving cluster has no such luxury.

Pure stdlib — loadable by file path from ``tools/`` without jax.
"""

from __future__ import annotations

import statistics
import time
from typing import Any, Callable, Mapping, Optional, Sequence

PING = "clock_ping"
PONG = "clock_pong"

#: exchanges per sync — enough for a stable median, cheap enough to
#: run at cluster start and again whenever drift is suspected.
DEFAULT_EXCHANGES = 8


def estimate_offset(samples: Sequence[tuple]) -> dict:
    """The pure math over ``(t0, t_remote, t1)`` exchange stamps; split
    out so tests can pin it against hand-computed skews."""
    if not samples:
        raise ValueError("clock sync needs at least one exchange")
    offs = sorted(t_remote - (t0 + t1) / 2.0
                  for t0, t_remote, t1 in samples)
    rtts = [t1 - t0 for t0, _t, t1 in samples]
    min_rtt = max(0.0, min(rtts))
    return {
        "offset_s": round(statistics.median(offs), 9),
        "uncertainty_s": round(min_rtt / 2.0, 9),
        "min_rtt_s": round(min_rtt, 9),
        "n": len(samples),
    }


def sync_server_step(endpoint, client: int, *,
                     clock: Callable[[], float] = time.time) -> None:
    """Answer ONE ping from ``client``. The reply is stamped as late
    as possible (right before the send) so the server-side dwell sits
    in the client's RTT, not in the offset."""
    msg = endpoint.recv_obj(client)
    if not isinstance(msg, Mapping) or msg.get("kind") != PING:
        raise ValueError(
            f"clock sync: expected a {PING!r} from rank {client}, got "
            f"{type(msg).__name__}"
        )
    endpoint.send_obj({"kind": PONG, "i": msg.get("i"),
                       "t": float(clock())}, client)


def sync_server(endpoint, client: int, n: int = DEFAULT_EXCHANGES, *,
                clock: Callable[[], float] = time.time) -> None:
    """The server half: answer ``n`` pings from ``client`` (blocking
    transports only — in-process hubs drive :func:`sync_server_step`
    through the client's ``pump``)."""
    for _ in range(n):
        sync_server_step(endpoint, client, clock=clock)


def sync_client(endpoint, server: int, n: int = DEFAULT_EXCHANGES, *,
                pump: Optional[Callable[[], Any]] = None,
                clock: Callable[[], float] = time.time) -> dict:
    """The client half: run ``n`` ping/pong exchanges against
    ``server``, estimate this process's offset TO the server's clock
    (``offset_s`` = server − client: ADD it to local epoch stamps to
    land on the server's timeline), and emit one ``clock_sync`` event
    when a recorder is active. ``pump`` (in-process hubs) is called
    between send and recv to run the server's answering half —
    loopback ``recv_obj`` is loud-not-blocking by design."""
    if n < 1:
        raise ValueError(f"need at least one exchange, got {n}")
    samples = []
    for i in range(n):
        t0 = float(clock())
        endpoint.send_obj({"kind": PING, "i": i}, server)
        if pump is not None:
            pump()
        reply = endpoint.recv_obj(server)
        t1 = float(clock())
        if not isinstance(reply, Mapping) or reply.get("kind") != PONG:
            raise ValueError(
                f"clock sync: expected a {PONG!r} from rank {server}, "
                f"got {type(reply).__name__}"
            )
        samples.append((t0, float(reply["t"]), t1))
    est = estimate_offset(samples)
    # Local import: tools/ loads this module by file path, where the
    # package-absolute import would pull the whole package (and jax).
    if __package__:
        from chainermn_tpu.observability import trace as _trace

        rec = _trace.active()
        if rec is not None:
            rec.event("clock_sync", peer=int(server), **est)
    return est


__all__ = [
    "DEFAULT_EXCHANGES",
    "PING",
    "PONG",
    "estimate_offset",
    "sync_client",
    "sync_server",
    "sync_server_step",
]
