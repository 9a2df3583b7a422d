"""``serve`` — the serving-side CLI (counterpart of ``training/cli.py``).

Starts a continuous-batching engine for a ``TransformerLM`` and exposes it
over a messaging transport::

    # TCP server: waits for --clients client processes on --port
    python -m distributed_ml_pytorch_tpu.serving.cli --port 29600 --clients 1

    # restore trained params (examples/train_lm.py checkpoint)
    python -m distributed_ml_pytorch_tpu.serving.cli --ckpt-dir /tmp/lm ...

    # a model from a published configuration file (a Hugging Face
    # ``config.json``; ``model_type`` ``olmo_hybrid`` builds
    # ``models/hybrid.HybridLM``, ``deepseek_v3``
    # ``models/latent_moe.LatentMoELM``), seeded random weights
    python -m distributed_ml_pytorch_tpu.serving.cli --model-config config.json --demo 4

    # self-contained demo: an in-process client drives N mixed
    # greedy/sampled requests through the full frontend path, prints the
    # SLO summary, exits (what the CLI tests run)
    python -m distributed_ml_pytorch_tpu.serving.cli --demo 6

Engine knobs: ``--slots`` (concurrent sequences), ``--cache-size`` (rows
per slot: prompt + padded decode blocks), ``--decode-block`` (tokens per
compiled block — admission latency vs merge amortization), ``--kv-quant``
(int8 slot caches: half the pool HBM, see the single-prefill note in
``serving/cache.py``), ``--max-queue`` (backpressure threshold),
``--prefill-bucket`` (prompt-length bucketing: compile count vs pad waste).
"""

from __future__ import annotations

import argparse
import sys


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        description="Continuous-batching TransformerLM serving engine")
    # model size (mirrors examples/generate_text.py)
    p.add_argument("--vocab", type=int, default=512)
    p.add_argument("--d-model", type=int, default=128)
    p.add_argument("--n-heads", type=int, default=8)
    p.add_argument("--n-layers", type=int, default=2)
    p.add_argument("--d-ff", type=int, default=256)
    p.add_argument("--max-len", type=int, default=0,
                   help="learned-position table size (0 = derived from "
                        "--cache-size; checkpoint restores must match the "
                        "training run's table)")
    p.add_argument("--dtype", default="float32",
                   choices=["float32", "bfloat16"])
    p.add_argument("--pos-encoding", default="learned",
                   choices=["learned", "rope"])
    p.add_argument("--model-config", type=str, default="", metavar="PATH",
                   help="build the model from a published config.json "
                        "(model_type olmo_hybrid -> models/hybrid.HybridLM, "
                        "deepseek_v3 -> models/latent_moe.LatentMoELM) "
                        "instead of the size flags above; --dtype applies")
    p.add_argument("--ckpt-dir", type=str, default="",
                   help="restore params from an examples/train_lm.py orbax "
                        "checkpoint (default: fresh random init)")
    # engine
    p.add_argument("--slots", type=int, default=4,
                   help="concurrent sequences sharing the compiled decode step")
    p.add_argument("--cache-size", type=int, default=256,
                   help="KV rows per slot (bounds prompt + generation)")
    p.add_argument("--decode-block", type=int, default=16,
                   help="tokens per compiled decode block (admission happens "
                        "between blocks)")
    p.add_argument("--kv-quant", action="store_true",
                   help="int8 slot caches with per-key scales — half the "
                        "pool footprint")
    p.add_argument("--max-queue", type=int, default=64,
                   help="queued-request cap; beyond it submissions are "
                        "rejected (backpressure)")
    p.add_argument("--prefill-bucket", type=int, default=16,
                   help="round prompt lengths up to this multiple for "
                        "prefill compilation (1 = exact lengths)")
    # transport
    p.add_argument("--port", type=str, default="29600",
                   help="TCP port the engine's rank-0 hub binds")
    p.add_argument("--master", type=str, default="localhost")
    p.add_argument("--clients", type=int, default=1,
                   help="client processes the TCP rendezvous waits for "
                        "(clients may later drop and rejoin)")
    p.add_argument("--reliable", action="store_true",
                   help="wrap the hub transport in the reliability layer "
                        "(seq + CRC + ack/retry + dedup, utils/messaging."
                        "ReliableTransport); clients must wrap too")
    p.add_argument("--client-deadline", type=float, default=30.0,
                   metavar="SEC",
                   help="cancel + free a request whose client has been "
                        "silent this long (disconnect/abandon cleanup); "
                        "streaming clients refresh liveness via StreamAck")
    p.add_argument("--coord", type=str, default="", metavar="HOST:PORT",
                   help="register this engine with an elastic control plane "
                        "(coord/cli.py): lease-based membership, and the "
                        "frontend holds submits while the coordinator "
                        "reports the engine fleet down, re-admitting them "
                        "on recovery")
    p.add_argument("--coord-rank", type=int, default=0, metavar="R",
                   help="this engine's rank in the coordination star "
                        "(0 = derive from --port; two engines MUST use "
                        "distinct ranks or the later one replaces the "
                        "earlier in the coordinator's membership)")
    p.add_argument("--demo", type=int, default=0, metavar="N",
                   help="serve N synthetic requests from an in-process "
                        "client, print the SLO summary, exit")
    # fleet serving (ISSUE 6): N engine replicas behind one router
    p.add_argument("--fleet", type=int, default=0, metavar="N",
                   help="run N engine replicas behind a FleetRouter "
                        "(occupancy + session-affinity routing, stream "
                        "migration across engine death, overload "
                        "shed/brownout); 0 = single-engine frontend")
    p.add_argument("--slo-ttft-ms", type=float, default=0.0,
                   help="TTFT SLO in ms (0 = off): recent TTFT above it "
                        "reads as overload and sheds lowest-priority work")
    p.add_argument("--shed-occupancy", type=float, default=0.0,
                   help="fleet pressure (busy+queued per slot) at which "
                        "new work admits only by displacing lower-priority "
                        "waiting work (0 = off); shed = explicit reject")
    p.add_argument("--brownout-occupancy", type=float, default=0.0,
                   help="pressure at which incoming max_new_tokens is "
                        "capped at --brownout-max-new (degrade before "
                        "shedding; 0 = off)")
    p.add_argument("--brownout-max-new", type=int, default=0)
    p.add_argument("--metrics-dump", type=str, default="", metavar="PATH",
                   help="write the metrics-registry snapshot JSON "
                        "(utils/metrics.get_registry, ISSUE 12) at exit — "
                        "engine SLO summary, transport counters; '-' "
                        "prints to stdout")
    p.add_argument("--seed", type=int, default=0)
    return p


def _build_model(args, parser):
    import jax
    import jax.numpy as jnp

    from distributed_ml_pytorch_tpu.models import TransformerLM

    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    if args.model_config:
        if args.ckpt_dir:
            parser.error("--model-config builds seeded weights; it takes no --ckpt-dir")
        lm = _model_from_config(args.model_config, dtype, parser)
        params = lm.init(
            jax.random.key(args.seed), jnp.zeros((1, 8), jnp.int32))["params"]
        return lm, params
    if args.d_model % args.n_heads:
        parser.error(f"--d-model {args.d_model} must divide by --n-heads "
                     f"{args.n_heads}")
    lm = TransformerLM(
        vocab_size=args.vocab, d_model=args.d_model, n_heads=args.n_heads,
        n_layers=args.n_layers, d_ff=args.d_ff,
        max_len=args.max_len or max(args.cache_size, 256),
        dtype=dtype, pos_encoding=args.pos_encoding,
    )
    if not args.ckpt_dir:
        params = lm.init(
            jax.random.key(args.seed), jnp.zeros((1, 8), jnp.int32)
        )["params"]
    else:
        import optax

        from distributed_ml_pytorch_tpu.parallel.seq_parallel import (
            create_lm_train_state,
        )
        from distributed_ml_pytorch_tpu.utils.checkpoint import Checkpointer

        with Checkpointer(args.ckpt_dir) as ckpt:
            step = ckpt.latest_step()
            if step is None:
                raise SystemExit(
                    f"no checkpoint under {args.ckpt_dir} — train one with "
                    "examples/train_lm.py --ckpt-dir first")
            template = jax.eval_shape(lambda: create_lm_train_state(
                lm, jax.random.key(args.seed), optax.sgd(0.1)))
            state, step = ckpt.restore(template)
            params = state.params
            print(f"restored params from step {step} of {args.ckpt_dir}")
    return lm, params


def _model_from_config(path: str, dtype, parser):
    """The model class a published ``config.json`` names by ``model_type``."""
    import json

    from distributed_ml_pytorch_tpu.models.hybrid import HybridLM
    from distributed_ml_pytorch_tpu.models.latent_moe import LatentMoELM

    builders = {"olmo_hybrid": HybridLM.from_config,
                "deepseek_v3": LatentMoELM.from_config}
    with open(path) as fh:
        cfg = json.load(fh)
    kind = cfg.get("model_type")
    if kind not in builders:
        parser.error(f"--model-config: model_type {kind!r} is not one of "
                     f"{sorted(builders)}")
    return builders[kind](cfg, dtype=dtype)


def _make_engine(lm, params, args):
    from distributed_ml_pytorch_tpu.serving.engine import ServingEngine

    return ServingEngine(
        lm, params, slots=args.slots, cache_size=args.cache_size,
        decode_block=args.decode_block, kv_quant=args.kv_quant,
        max_queue=args.max_queue, prefill_bucket=args.prefill_bucket)


def _build_engine(args, parser):
    lm, params = _build_model(args, parser)
    engine = _make_engine(lm, params, args)
    # observability (ISSUE 12): the engine's SLO summary rides the
    # process registry, so --metrics-dump sees serving health for free
    from distributed_ml_pytorch_tpu.utils.metrics import get_registry

    get_registry().attach("engine", engine.slo_summary)
    return engine


def _build_fleet(args, parser, coord_factory=None):
    """N engine replicas as started EngineMembers (one model init, shared
    read-only params). ``coord_factory(engine_id)`` may supply a
    CoordClient per member (lease-holding fleet membership). Engines are
    WARMED (prefill buckets + decode block compiled) before their serve
    threads start, so the router's liveness probe never mistakes a
    cold-start XLA compile for a death."""
    import numpy as np

    from distributed_ml_pytorch_tpu.serving.fleet import EngineMember

    lm, params = _build_model(args, parser)
    members = []
    for i in range(args.fleet):
        engine = _make_engine(lm, params, args)
        # EVERY bucket the cache can hold: a first-of-its-size prompt
        # compiling inside the serve loop would stall heartbeats and read
        # as a death (compiled programs are shared across same-shape
        # replicas, so only replica 0 pays the XLA time)
        bucket = max(2, args.prefill_bucket)
        warmed = 0
        while warmed < 32 and engine.pool.capacity_needed(bucket, bucket, 2) \
                <= engine.pool.cache_size:
            # (capped: --prefill-bucket 1 means exact-length buckets, where
            # exhaustive warmup is unbounded — residual lazy compiles are
            # that configuration's accepted cost)
            w = engine.submit(np.zeros(bucket, np.int32), 2)
            engine.run_until_idle()
            assert w.done
            bucket += max(1, args.prefill_bucket)
            warmed += 1
        engine.reset_metrics()
        coord = coord_factory(i) if coord_factory is not None else None
        members.append(EngineMember(i, engine, coord=coord).start())
    return members


def _print_summary(engine) -> None:
    import json

    summary = engine.slo_summary()
    print("SLO summary:", json.dumps(summary, indent=2, default=float))


def _run_demo(args, engine=None, members=None) -> int:
    import threading

    import numpy as np

    from distributed_ml_pytorch_tpu.serving.frontend import (
        ServingClient,
        ServingFrontend,
    )
    from distributed_ml_pytorch_tpu.utils.messaging import InProcessTransport

    world = InProcessTransport.create_world(2)
    if members is not None:
        from distributed_ml_pytorch_tpu.serving.fleet import FleetRouter

        frontend = FleetRouter(
            world[0], members, slo_ttft_ms=args.slo_ttft_ms,
            shed_occupancy=args.shed_occupancy,
            brownout_occupancy=args.brownout_occupancy,
            brownout_max_new=args.brownout_max_new)
        engine = members[0].engine  # SLO summary target below
    else:
        frontend = ServingFrontend(engine, world[0])
    client = ServingClient(world[1])
    server = threading.Thread(target=frontend.serve_forever, daemon=True)
    server.start()

    rng = np.random.default_rng(args.seed)
    # cap generation lengths so every demo request fits the slot capacity
    # check in ServingEngine.submit (bucketed prompt + whole decode blocks)
    budget = max(
        2, min(24, args.cache_size - args.prefill_bucket - args.decode_block))
    try:
        # submit everything up front so the engine actually batches the
        # requests together, then collect the streams
        submitted = []
        for i in range(args.demo):
            prompt = rng.integers(
                0, args.vocab, size=int(rng.integers(2, 12))).astype(np.int32)
            new = int(rng.integers(2, budget + 1))
            sampled = bool(i % 2)
            rid = client.submit(
                prompt, new,
                temperature=0.8 if sampled else 0.0,
                top_k=8 if sampled else 0, seed=int(i))
            submitted.append((rid, new))
        results = {
            rid: (new, list(client.stream(rid, timeout=120.0)))
            for rid, new in submitted
        }
        for rid, (new, toks) in results.items():
            if len(toks) != new or any(t < 0 or t >= args.vocab for t in toks):
                print(f"demo request {rid}: bad stream {toks}", file=sys.stderr)
                return 1
        print(f"served {args.demo} demo requests "
              f"({sum(len(t) for _, t in results.values())} tokens)")
        _print_summary(engine)
        if members is not None:
            import json

            print("fleet summary:",
                  json.dumps(frontend.fleet_summary(), default=str))
        print("serving demo complete")
        return 0
    finally:
        frontend.stop()
        server.join(timeout=5)
        for t in world.values():
            t.close()


def _main_fleet(args, parser) -> int:
    """N replicas behind a FleetRouter (``--fleet N``): the quickstart is
    ``make serve-fleet``; add ``--coord host:port`` for lease-holding
    membership + coordinator-driven scaling advice."""
    coord_factory = None
    coord_clients = []
    if args.coord:
        from distributed_ml_pytorch_tpu.coord.member import CoordClient
        from distributed_ml_pytorch_tpu.utils.messaging import TCPTransport

        host, _, cport = args.coord.partition(":")

        def coord_factory(i):
            # engines live in the high end of the coordination rank space
            # (see the single-engine path below); co-hosted replicas offset
            # by engine id so each holds its OWN lease
            rank = (args.coord_rank or 50 + int(args.port) % 14) + i
            if rank >= 64:
                # the coordination star validates 1 <= rank < world_size
                # (64): an overflowing derived rank would be refused at the
                # hub's hello and the replica would silently serve without
                # a lease — fail loudly instead
                parser.error(
                    f"fleet replica {i} derives coordination rank {rank} "
                    ">= 64 — pin a lower base with --coord-rank")
            client = CoordClient(
                # distcheck: ignore[DC105] same advisory control star as
                # the single-engine path — periodic, self-healing frames
                TCPTransport(rank=rank, world_size=64,
                             master=host or "localhost",
                             port=int(cport or 29700)),
                "engine")
            coord_clients.append(client)
            return client

    members = _build_fleet(args, parser, coord_factory)
    try:
        if args.demo:
            return _run_demo(args, members=members)

        from distributed_ml_pytorch_tpu.serving.fleet import FleetRouter
        from distributed_ml_pytorch_tpu.utils.messaging import (
            ReliableTransport,
            TCPTransport,
        )

        transport = TCPTransport(
            rank=0, world_size=1 + args.clients, master=args.master,
            port=int(args.port))
        if args.reliable:
            transport = ReliableTransport(transport)
        router = FleetRouter(
            transport, members,
            client_deadline=args.client_deadline,
            fleet=members[0].coord.fleet if members[0].coord else None,
            slo_ttft_ms=args.slo_ttft_ms,
            shed_occupancy=args.shed_occupancy,
            brownout_occupancy=args.brownout_occupancy,
            brownout_max_new=args.brownout_max_new)
        print(f"fleet serving on {args.master}:{args.port} "
              f"({args.fleet} engines x {args.slots} slots x "
              f"{args.cache_size} rows, block {args.decode_block})")
        try:
            router.serve_forever()
        except KeyboardInterrupt:
            pass
        finally:
            router.stop()
            transport.close()
            import json

            print("fleet summary:",
                  json.dumps(router.fleet_summary(), default=str))
            _print_summary(members[0].engine)
        return 0
    finally:
        for m in members:
            if m.alive:
                m.stop()
        for c in coord_clients:
            c.transport.close()


def main(argv=None) -> int:
    parser = build_parser()
    args = parser.parse_args(argv)
    try:
        return _main(args, parser)
    finally:
        from distributed_ml_pytorch_tpu.runtime import startup

        startup.report_compile_cache("serving engine")
        # observability plane (ISSUE 12): one registry snapshot at exit
        if getattr(args, "metrics_dump", ""):
            from distributed_ml_pytorch_tpu.coord.cli import dump_metrics

            dump_metrics(args.metrics_dump)


def _main(args, parser) -> int:
    print(args)
    from distributed_ml_pytorch_tpu.runtime import startup

    startup.enable_compile_cache()
    startup.announce_devices("serving engine")
    if args.fleet:
        return _main_fleet(args, parser)
    engine = _build_engine(args, parser)
    if args.demo:
        return _run_demo(args, engine=engine)

    from distributed_ml_pytorch_tpu.serving.frontend import ServingFrontend
    from distributed_ml_pytorch_tpu.utils.messaging import (
        ReliableTransport,
        TCPTransport,
    )

    coord_client = None
    if args.coord:
        from distributed_ml_pytorch_tpu.coord.member import CoordClient

        host, _, cport = args.coord.partition(":")
        # engines live in the high end of the coordination rank space so
        # they can never collide with training ranks (rank + 1 there);
        # deriving from the SERVING port keeps co-hosted engines distinct
        # (two engines cannot share a port) — cross-host fleets should pin
        # --coord-rank explicitly
        rank = args.coord_rank or 50 + int(args.port) % 14
        coord_client = CoordClient(
            # distcheck: ignore[DC105] coordination frames are periodic and
            # self-healing (join retries, lease renewals the reliability
            # layer exempts anyway); --reliable hardens the DATA hub below,
            # not the advisory control star
            TCPTransport(rank=rank, world_size=64,
                         master=host or "localhost",
                         port=int(cport or 29700)),
            "engine")
        coord_client.join(timeout=10)
    transport = TCPTransport(
        rank=0, world_size=1 + args.clients, master=args.master,
        port=int(args.port))
    if args.reliable:
        transport = ReliableTransport(transport)
    frontend = ServingFrontend(
        engine, transport, client_deadline=args.client_deadline,
        fleet=coord_client.fleet if coord_client is not None else None,
        slo_ttft_ms=args.slo_ttft_ms, shed_occupancy=args.shed_occupancy,
        brownout_occupancy=args.brownout_occupancy,
        brownout_max_new=args.brownout_max_new)
    print(f"serving on {args.master}:{args.port} "
          f"({args.slots} slots x {args.cache_size} rows, "
          f"block {args.decode_block}"
          + (", int8 kv" if args.kv_quant else "") + ")")
    try:
        frontend.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        frontend.stop()
        transport.close()
        if coord_client is not None:
            coord_client.close()
            coord_client.transport.close()
        _print_summary(engine)
    return 0


if __name__ == "__main__":
    sys.exit(main())
