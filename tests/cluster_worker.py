"""Worker for ``test_mp_disaggregated_handoff_over_tcp`` (ISSUE 8):
rank 0 is a prefill replica, rank 1 a decode replica, KV payloads
cross REAL process boundaries over the native TCP plane — the
multi-process form of the handoff the in-process loopback tests
rehearse. Both ranks init identical params (same seed, CPU backend),
so rank 1 can check every adopted stream against its own sequential
``generate`` reference.

With ``CHAINERMN_TPU_JOURNEY_DIR`` set (ISSUE 17:
``test_mp_journey_merge_over_tcp``) each rank additionally records a
per-rank JSONL trace there, the ranks run a real clock-sync exchange
over the same TCP plane, and the journey context rides the KV payloads
— afterwards the test merges the two files and checks every request
reconstructs to one complete cross-PROCESS causal chain."""

import os
import sys
import time

sys.path.insert(0, __file__.rsplit("/tests/", 1)[0])
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from chainermn_tpu.models.transformer import (  # noqa: E402
    TransformerLM,
    generate,
)
from chainermn_tpu.native.tcp_comm import TcpHostComm  # noqa: E402
from chainermn_tpu.serving import (  # noqa: E402
    Request,
    Scheduler,
    ServingEngine,
)
from chainermn_tpu.serving.cluster import recv_kv, send_kv  # noqa: E402

VOCAB = 32
N_REQUESTS = 4


def build():
    model = TransformerLM(
        vocab_size=VOCAB, num_layers=2, num_heads=4, d_model=16,
        d_ff=32, max_len=64, compute_dtype=jnp.float32,
    )
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32), train=False
    )
    engine = ServingEngine(
        model, params, num_slots=N_REQUESTS, max_len=64,
        decode_impl="paged", kv_block_size=8, prefill_buckets=(4, 8, 16),
    )
    rs = np.random.RandomState(21)
    shared = rs.randint(1, VOCAB, size=10).tolist()
    reqs = [
        (shared + rs.randint(1, VOCAB, size=int(rs.randint(2, 5))
                             ).tolist(), int(rs.randint(2, 5)))
        for _ in range(N_REQUESTS)
    ]
    return model, params, engine, reqs


def main():
    rank, size, coord = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
    assert size == 2
    journey_dir = os.environ.get("CHAINERMN_TPU_JOURNEY_DIR")
    if journey_dir:
        # Per-rank trace file + the rank stamp the recorder reads —
        # BEFORE the recorder exists.
        os.environ["CHAINERMN_TPU_RANK"] = str(rank)
        from chainermn_tpu.observability import clocksync, journey, trace
        rec = trace.enable(os.path.join(journey_dir,
                                        f"rank{rank}.jsonl"))
    comm = TcpHostComm(rank, size, coord)
    model, params, engine, reqs = build()

    if journey_dir:
        # Real two-process clock sync over the same TCP plane the KV
        # payloads ride: rank 1's trace gains the clock_sync event the
        # merge uses to align rank-0 stamps.
        if rank == 0:
            clocksync.sync_server(comm, 1)
        else:
            clocksync.sync_client(comm, 0)

    if rank == 0:
        for i, (prompt, _gen) in enumerate(reqs):
            slot, _tok, _bucket = engine.prefill_join(prompt)
            payload = engine.export_kv(slot)
            engine.leave(slot)
            if journey_dir:
                # Hop 0 on the prefill rank; the ADVANCED snapshot
                # rides the payload so rank 1 parents onto this span.
                ctx = journey.new(f"mp{i}")
                rec.event("route", request=f"mp{i}", replica=1,
                          **ctx.begin_hop())
                payload[journey.WIRE_KEY] = ctx.to_wire()
            send_kv(comm, payload, 1)
        assert comm.recv_obj(1) == "adopted"
    else:
        sched = Scheduler(engine)
        sched.start_window()
        for i, (prompt, gen) in enumerate(reqs):
            req = Request(prompt=prompt, max_new_tokens=gen,
                          request_id=f"mp{i}")
            # Arrival stamps BEFORE the receive so the wire+adoption
            # time sits inside TTFT (the router stamps at submit the
            # same way).
            req._arrival = time.perf_counter()
            payload = recv_kv(comm, 0)
            res = engine.import_kv(payload)
            assert res is not None, "pool sized for the full burst"
            slot, tok = res
            handoff_s = None
            if journey_dir:
                journey.adopt_payload(req, payload)
                handoff_s = round(time.perf_counter() - req._arrival, 9)
                rec.event("kv_transfer", request=f"mp{i}", src=0,
                          nbytes=payload.get("nbytes"),
                          dur_s=handoff_s, **journey.fields(req))
            sched.admit_prefilled(req, slot, tok, dur_s=handoff_s)
        comm.send_obj("adopted", 0)
        while not sched.drained:
            sched.tick()
        sched.close_window()
        for i, (prompt, gen) in enumerate(reqs):
            ref = np.asarray(generate(
                model, params, jnp.asarray([prompt], jnp.int32),
                len(prompt) + gen,
            ))[0].tolist()
            got = sched.results[f"mp{i}"]["tokens"]
            assert got == ref, (i, got, ref)

    comm.barrier()
    comm.finalize()
    if journey_dir:
        trace.disable()  # flush + close the per-rank JSONL
    print(f"CLUSTER_WORKER_OK {rank}")


if __name__ == "__main__":
    main()
