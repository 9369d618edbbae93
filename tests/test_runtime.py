"""Distributed runtime tests: component model, discovery, leases, streaming.

Modeled on the reference's runtime test strategy (SURVEY.md §4.2): closure
engines + in-memory control plane for most tests; a real TCP control-plane
server for the transport-integration tests (the analogue of the reference's
gated etcd/NATS tests, but self-contained so they always run).
"""
import asyncio

import pytest

from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.runtime.transports.memory import MemoryPlane
from dynamo_tpu.runtime.transports.server import ControlPlaneServer


def run(coro):
    return asyncio.run(coro)


async def echo_engine(request, context):
    for i in range(int(request.get("n", 3))):
        if context.is_stopped:
            return
        yield {"i": i, "text": request.get("text", "")}


def test_serve_and_generate_memory_plane():
    async def main():
        plane = MemoryPlane()
        server_rt = await DistributedRuntime.create_local(plane, "worker1")
        client_rt = await DistributedRuntime.create_local(plane, "client1")
        ep = server_rt.namespace("ns").component("echo").endpoint("generate")
        await ep.serve(echo_engine)

        client = client_rt.namespace("ns").component("echo").endpoint(
            "generate").client()
        await client.start()
        await client.wait_for_instances()
        frames = []
        async for frame in await client.generate({"n": 4, "text": "hi"}):
            frames.append(frame)
        assert [f["i"] for f in frames] == [0, 1, 2, 3]
        assert frames[0]["text"] == "hi"
        await client_rt.shutdown()
        await server_rt.shutdown()

    run(main())


def test_routing_policies_and_direct():
    async def main():
        plane = MemoryPlane()
        rts = []
        for wid in ("w1", "w2"):
            rt = await DistributedRuntime.create_local(plane, wid)
            ep = rt.namespace("ns").component("c").endpoint("gen")

            async def engine(request, context, wid=wid):
                yield {"worker": wid}

            await ep.serve(engine)
            rts.append(rt)
        crt = await DistributedRuntime.create_local(plane, "cl")
        client = crt.namespace("ns").component("c").endpoint("gen").client()
        await client.start()
        await client.wait_for_instances()
        assert client.instance_ids() == ["w1", "w2"]

        # direct routing hits the requested instance
        for wid in ("w1", "w2"):
            frames = [f async for f in await client.direct({}, wid)]
            assert frames == [{"worker": wid}]

        # round robin alternates
        seen = []
        for _ in range(4):
            frames = [f async for f in await client.round_robin({})]
            seen.append(frames[0]["worker"])
        assert set(seen) == {"w1", "w2"}
        for rt in rts + [crt]:
            await rt.shutdown()

    run(main())


def test_instance_removed_on_shutdown():
    async def main():
        plane = MemoryPlane()
        rt1 = await DistributedRuntime.create_local(plane, "w1")
        ep = rt1.namespace("ns").component("c").endpoint("gen")
        await ep.serve(echo_engine)
        crt = await DistributedRuntime.create_local(plane, "cl")
        client = crt.namespace("ns").component("c").endpoint("gen").client()
        await client.start()
        await client.wait_for_instances()
        assert client.instance_ids() == ["w1"]
        await rt1.shutdown()
        await asyncio.sleep(0.05)  # watch event propagation
        assert client.instance_ids() == []
        await crt.shutdown()

    run(main())


def test_client_watch_stream_death_recovers_and_converges():
    """Satellite regression: a killed watch stream must not leave a
    SILENT dead watcher. The pump resumes with backoff + jitter and
    resyncs from a full snapshot — registrations AND deregistrations
    that happened during the gap converge."""
    from dynamo_tpu.runtime import faults
    from dynamo_tpu.runtime.cpstats import CP_STATS

    async def main():
        plane = MemoryPlane()
        rt1 = await DistributedRuntime.create_local(plane, "w1")
        await rt1.namespace("ns").component("c").endpoint("gen").serve(
            echo_engine)
        crt = await DistributedRuntime.create_local(plane, "cl")
        client = crt.namespace("ns").component("c").endpoint("gen").client()
        await client.start()
        await client.wait_for_instances()
        resyncs_before = CP_STATS.watch_resyncs

        # kill the next watch delivery: the stream raises into the pump
        faults.REGISTRY.arm("watch.stream", faults.FaultSchedule(
            0, [faults.FaultSpec("fail_n", n=1)]))
        # both events die WITH the stream; only the resync can recover them
        rt2 = await DistributedRuntime.create_local(plane, "w2")
        await rt2.namespace("ns").component("c").endpoint("gen").serve(
            echo_engine)
        await rt1.shutdown()   # w1 deregisters during the gap

        deadline = asyncio.get_running_loop().time() + 10
        while client.instance_ids() != ["w2"]:
            assert asyncio.get_running_loop().time() < deadline, \
                client.instances
            await asyncio.sleep(0.05)
        assert CP_STATS.watch_resyncs > resyncs_before
        faults.REGISTRY.disarm()

        # the resumed watcher is LIVE, not just resynced: later events
        # flow again without further faults
        await rt2.shutdown()
        deadline = asyncio.get_running_loop().time() + 5
        while client.instance_ids():
            assert asyncio.get_running_loop().time() < deadline
            await asyncio.sleep(0.05)
        await crt.shutdown()

    try:
        run(asyncio.wait_for(main(), 60))
    finally:
        from dynamo_tpu.runtime import faults
        faults.REGISTRY.disarm()
        faults.REGISTRY.reset_counters()


def test_client_watch_batch_coalesces_flaps():
    """A churn tick's events coalesce per key: N put/delete flaps on one
    key apply as ONE final state (and the coalesce counter advances)."""
    from dynamo_tpu.runtime.cpstats import CP_STATS

    async def main():
        plane = MemoryPlane()
        crt = await DistributedRuntime.create_local(plane, "cl")
        client = crt.namespace("ns").component("c").endpoint("gen").client()
        await client.start()
        seen = []
        client.add_listener(lambda kind, wid, info: seen.append((kind, wid)))
        CP_STATS.reset()
        # burst of flaps on one key, queued BEFORE the pump can tick:
        # the batch must fold to the final put
        key = "ns/components/c/gen:wf"
        import json as _json
        for i in range(9):
            await plane.kv.put(key, _json.dumps({"i": i}).encode())
        deadline = asyncio.get_running_loop().time() + 5
        while "wf" not in client.instances:
            assert asyncio.get_running_loop().time() < deadline
            await asyncio.sleep(0.02)
        assert client.instances["wf"]["i"] == 8   # final state won
        # fewer listener fires than raw events — the batching coalesced
        assert len([s for s in seen if s[1] == "wf"]) < 9
        assert CP_STATS.watch_events_coalesced > 0
        await crt.shutdown()

    run(asyncio.wait_for(main(), 30))


def test_lease_expiry_prunes_instances():
    """Killing keep-alive (by revoking through expiry path) removes keys —
    the reference's lease-TTL failure-detection behavior."""
    async def main():
        plane = MemoryPlane()
        lease = await plane.kv.grant_lease(ttl=0.15)
        await plane.kv.put("ns/components/c/gen:wX", b"{}", lease.id)
        assert await plane.kv.get("ns/components/c/gen:wX") is not None
        await asyncio.sleep(0.4)  # no keep-alive -> expiry
        assert await plane.kv.get("ns/components/c/gen:wX") is None
        assert lease.lost.is_set()

    run(main())


def test_expired_lease_is_reported_to_its_holder_over_tcp():
    """A holder whose event loop stalls past the TTL (a synchronous XLA
    compile on a chip) loses its keys server-side; its next keepalive must
    say so. It used to answer ok forever: the worker lived on unregistered
    and nothing reported it (PR 21, disagg graph on a v5e)."""
    async def main():
        server = await ControlPlaneServer(port=0).start()
        try:
            rt = await DistributedRuntime.connect("127.0.0.1", server.port)
            kv = rt.kv
            lease = await kv.grant_lease(ttl=0.3)
            await kv.put("ns/components/c/gen:w", b"{}", lease.id)
            # the stall: no heartbeat leaves this client for > TTL
            kv._keepalive_tasks.pop(lease.id).cancel()
            await asyncio.sleep(0.6)
            assert await kv.get("ns/components/c/gen:w") is None
            reply = await kv._rpc({"op": "lease_keepalive",
                                   "lease": lease.id})
            assert reply["ok"] is False
            await rt.shutdown()
        finally:
            await server.stop()

    run(asyncio.wait_for(main(), 30))


def test_cancellation_stops_stream():
    async def main():
        plane = MemoryPlane()
        srt = await DistributedRuntime.create_local(plane, "w")
        produced = []

        async def slow_engine(request, context):
            for i in range(1000):
                if context.is_stopped:
                    return
                produced.append(i)
                yield {"i": i}
                await asyncio.sleep(0.01)

        await srt.namespace("ns").component("c").endpoint("gen").serve(slow_engine)
        crt = await DistributedRuntime.create_local(plane, "cl")
        client = crt.namespace("ns").component("c").endpoint("gen").client()
        await client.start()
        ctx = Context()
        count = 0
        async for _ in await client.generate({"n": 1000}, ctx):
            count += 1
            if count == 5:
                ctx.stop_generating()
        await asyncio.sleep(0.2)
        assert count >= 5
        assert len(produced) < 1000  # engine observed the stop
        await crt.shutdown()
        await srt.shutdown()

    run(main())


def test_events_pub_sub():
    async def main():
        plane = MemoryPlane()
        rt = await DistributedRuntime.create_local(plane, "w")
        ns = rt.namespace("ns")
        sub = await ns.subscribe("kv_events")
        await ns.publish("kv_events", {"event_id": 1, "op": "stored"})
        subject, payload = await asyncio.wait_for(anext(sub), 1.0)
        assert subject == "ns.kv_events"
        assert payload["event_id"] == 1
        await rt.shutdown()

    run(main())


def test_stats_scrape():
    async def main():
        plane = MemoryPlane()
        rt = await DistributedRuntime.create_local(plane, "w1")
        ep = rt.namespace("ns").component("c").endpoint("gen")
        await ep.serve(echo_engine, stats_handler=lambda: {"load": 0.5})
        crt = await DistributedRuntime.create_local(plane, "cl")
        client = crt.namespace("ns").component("c").endpoint("gen").client()
        await client.start()
        await client.wait_for_instances()
        stats = await client.scrape_stats()
        assert stats == {"w1": {"load": 0.5}}
        await crt.shutdown()
        await rt.shutdown()

    run(main())


def test_keepalive_survives_slow_first_token(monkeypatch):
    """A responder whose first item takes longer than the requester's
    inactivity timeout must NOT be killed: keepalive frames prove liveness
    (VERDICT r2 weak #8)."""
    from dynamo_tpu.runtime import dataplane

    monkeypatch.setattr(dataplane, "KEEPALIVE_INTERVAL_S", 0.05)

    async def main():
        server = await dataplane.DataPlaneServer().start()
        stream = server.register()
        ctx = Context()

        async def slow_gen():
            await asyncio.sleep(0.5)  # >> per-frame timeout below
            yield b"tok"

        _, writer = await dataplane.call_home(
            server.connection_info, stream.stream_id, ctx)
        pump = asyncio.create_task(
            dataplane.pump_stream(writer, slow_gen(), ctx))
        # per-frame timeout far below the engine delay: only keepalives
        # keep this stream alive
        frames = [f async for f in server.stream_responses(
            stream, timeout=0.2)]
        assert frames == [b"tok"]
        await pump
        await server.stop()

    run(main())


def test_inactivity_raises_typed_error():
    """A responder that never connects (dead peer) surfaces as
    StreamInactiveError, not a bare timeout."""
    from dynamo_tpu.runtime import dataplane

    async def main():
        server = await dataplane.DataPlaneServer().start()
        stream = server.register()
        with pytest.raises(dataplane.StreamInactiveError):
            async for _ in server.stream_responses(stream, timeout=0.1):
                pass
        await server.stop()

    run(main())


# -- TCP control plane (integration, self-contained) --------------------------

def test_control_plane_durability(tmp_path):
    """ADVICE r2: control-plane state must survive a server death. Unleased
    KV (model registry, config) and work-queue contents (the JetStream-like
    prefill queue) are journaled and recovered; lease-scoped discovery keys
    are deliberately ephemeral (etcd semantics: leases die with the server,
    workers re-register on reconnect)."""
    data_dir = str(tmp_path / "cp")

    async def phase1():
        server = await ControlPlaneServer(port=0, data_dir=data_dir).start()
        try:
            rt = await DistributedRuntime.connect("127.0.0.1", server.port, "w")
            await rt.kv.put("models/m1", b"card1")
            await rt.kv.put("models/m2", b"card2")
            await rt.kv.delete("models/m2")
            lease = await rt.kv.grant_lease(10.0)
            await rt.kv.put("instances/w", b"ephemeral", lease.id)
            for i in range(3):
                await rt.messaging.queue_push("prefill", f"job{i}".encode())
            assert await rt.messaging.queue_pop("prefill", 1.0) == b"job0"
            await rt.shutdown()
        finally:
            await server.stop()

    async def phase2():
        server = await ControlPlaneServer(port=0, data_dir=data_dir).start()
        try:
            rt = await DistributedRuntime.connect("127.0.0.1", server.port, "w")
            assert await rt.kv.get("models/m1") == b"card1"
            assert await rt.kv.get("models/m2") is None
            assert await rt.kv.get("instances/w") is None  # lease-scoped
            assert await rt.messaging.queue_depth("prefill") == 2
            assert await rt.messaging.queue_pop("prefill", 1.0) == b"job1"
            await rt.shutdown()
        finally:
            await server.stop()

    run(phase1())
    run(phase2())


def test_queue_push_survives_sigkill(tmp_path):
    """VERDICT r3 #4: an ACKNOWLEDGED queue_push survives SIGKILL of the
    server process. The journal group-commits with fsync and the server
    acks a push only after its record reached stable storage (JetStream
    file-store semantics, SURVEY §L0) — so recovery must hold every item
    whose push returned, with at most the single in-flight unacked item
    beyond that."""
    import os
    import signal
    import subprocess
    import sys

    data_dir = str(tmp_path / "cp")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.Popen(
        [sys.executable, "-m", "dynamo_tpu.runtime.transports.server",
         "--host", "127.0.0.1", "--port", "0", "--data-dir", data_dir],
        stdout=subprocess.PIPE, text=True,
        env={**os.environ, "PYTHONPATH": repo})
    acked = []
    try:
        port = None
        for line in proc.stdout:
            if line.startswith("READY"):
                port = int(line.strip().rsplit(":", 1)[1])
                break
        assert port, "server never printed READY"

        async def push_then_kill():
            rt = await DistributedRuntime.connect("127.0.0.1", port, "w")
            try:
                for i in range(20):
                    await rt.messaging.queue_push("prefill",
                                                  f"job{i}".encode())
                    acked.append(i)
                    if i == 13:
                        # SIGKILL immediately after an ack, no grace: the
                        # acknowledged records must already be on disk
                        proc.send_signal(signal.SIGKILL)
                        return
            except (ConnectionError, OSError, asyncio.IncompleteReadError):
                pass  # server died mid-push: only acked items count
            finally:
                # close the runtime INSIDE this loop: transports/tasks
                # abandoned at asyncio.run teardown are finalized by GC
                # later — potentially during the NEXT test's loop, where
                # a transport __del__ can close a since-reused fd (seen
                # as a 30s+60s hang in whatever test follows)
                try:
                    await asyncio.wait_for(rt.shutdown(), 5)
                except Exception:
                    pass

        run(push_then_kill())
        proc.wait(timeout=10)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)

    assert len(acked) >= 1, "no push was ever acknowledged"
    from dynamo_tpu.runtime.transports.journal import DurablePlane
    plane = DurablePlane(data_dir)
    try:
        q = plane.messaging._queues["prefill"]
        items = list(q._queue)
        # every acknowledged push recovered, in order; at most one extra
        # in-flight (written-but-unacked) item may trail
        expect = [f"job{i}".encode() for i in acked]
        assert items[:len(expect)] == expect, (items, expect)
        assert len(items) <= len(expect) + 1, (items, expect)
    finally:
        plane.close()


def test_journal_compaction(tmp_path):
    """Snapshot compaction truncates the journal but preserves state."""
    from dynamo_tpu.runtime.transports.journal import DurablePlane

    async def main():
        plane = DurablePlane(str(tmp_path), compact_every=5)
        for i in range(12):  # crosses two compactions
            await plane.kv.put(f"k{i}", f"v{i}".encode())
        await plane.messaging.queue_push("q", b"x")
        plane.close()

        plane2 = DurablePlane(str(tmp_path))
        for i in range(12):
            assert await plane2.kv.get(f"k{i}") == f"v{i}".encode()
        assert await plane2.messaging.queue_depth("q") == 1
        plane2.close()

    run(main())


def test_compaction_crash_window_no_queue_duplication(tmp_path):
    """A crash between the snapshot rename and the journal truncation must
    not replay pre-compaction records onto the new snapshot (queue replay
    is not idempotent): the stale journal's generation header mismatches
    the snapshot and it is discarded (code-review r3)."""
    import shutil

    from dynamo_tpu.runtime.transports.journal import DurablePlane

    async def main():
        d = str(tmp_path)
        plane = DurablePlane(d, compact_every=1000)
        for item in (b"a", b"b", b"c"):
            await plane.messaging.queue_push("q", item)
        assert await plane.messaging.queue_pop("q", 1.0) == b"a"
        plane.journal.sync()  # flush-behind writer: settle before copying
        saved = d + "/journal.precompact"
        shutil.copy(plane.journal.journal_path, saved)
        plane.journal.compact()
        plane.journal.sync()
        # simulate the crash: the pre-compaction journal survives on disk
        shutil.copy(saved, plane.journal.journal_path)
        plane.close()

        plane2 = DurablePlane(d)
        assert await plane2.messaging.queue_depth("q") == 2
        assert await plane2.messaging.queue_pop("q", 1.0) == b"b"
        plane2.close()

    run(main())


def test_leased_put_shadowing_unleased_key_not_resurrected(tmp_path):
    """Overwriting a journaled unleased key with a lease-scoped value kills
    the old value for good — it must not resurrect on restart
    (code-review r3)."""
    from dynamo_tpu.runtime.transports.journal import DurablePlane

    async def main():
        d = str(tmp_path)
        plane = DurablePlane(d)
        await plane.kv.put("k", b"v1")
        lease = await plane.kv.grant_lease(10.0)
        await plane.kv.put("k", b"ephemeral", lease.id)
        plane.close()

        plane2 = DurablePlane(d)
        assert await plane2.kv.get("k") is None
        plane2.close()

    run(main())


def test_tcp_control_plane_end_to_end():
    async def main():
        server = await ControlPlaneServer(port=0).start()
        try:
            rt1 = await DistributedRuntime.connect("127.0.0.1", server.port, "w1")
            rt2 = await DistributedRuntime.connect("127.0.0.1", server.port, "c1")
            ep = rt1.namespace("ns").component("echo").endpoint("generate")
            await ep.serve(echo_engine)
            client = rt2.namespace("ns").component("echo").endpoint(
                "generate").client()
            await client.start()
            await client.wait_for_instances()
            frames = [f async for f in await client.generate({"n": 3, "text": "t"})]
            assert [f["i"] for f in frames] == [0, 1, 2]

            # queue semantics
            await rt1.messaging.queue_push("q1", b"job1")
            assert await rt2.messaging.queue_depth("q1") == 1
            assert await rt2.messaging.queue_pop("q1", timeout=1.0) == b"job1"
            assert await rt2.messaging.queue_pop("q1", timeout=0.05) is None

            # kv watch across connections
            snapshot, events = await rt2.kv.watch_prefix("models/")
            assert snapshot == []
            await rt1.kv.put("models/m1", b"v1")
            ev = await asyncio.wait_for(anext(events), 2.0)
            assert (ev.kind, ev.key, ev.value) == ("put", "models/m1", b"v1")
            await rt1.shutdown()
            await rt2.shutdown()
        finally:
            await server.stop()

    run(main())


def test_a_request_in_its_handshake_survives_a_collection(monkeypatch):
    """The control-plane client holds the requests it is handling. The
    loop holds tasks weakly, and a handler waiting for the handshake of
    a stream it dialled itself (dataplane.call_home) is reachable from
    that stream alone, whose protocol holds its reader weakly: a
    collection in that moment destroyed the task pending ("Task was
    destroyed but it is pending!") and the caller ran into its ack
    timeout. test_tcp_control_plane_end_to_end failed so on every tree up
    to PR 44, whenever the two tests before it had moved the collector's
    count to that moment; here the collection is made there."""
    import gc

    from dynamo_tpu.runtime import component, dataplane
    on_connect = dataplane.DataPlaneServer._on_connect

    async def collect_first(self, reader, writer):
        # the responder has sent its hello and waits for the answer
        await asyncio.sleep(0.05)
        gc.collect()
        await on_connect(self, reader, writer)

    monkeypatch.setattr(dataplane.DataPlaneServer, "_on_connect",
                        collect_first)
    monkeypatch.setattr(component, "DISPATCH_ACK_TIMEOUT_S", 5.0)

    async def main():
        server = await ControlPlaneServer(port=0).start()
        try:
            rt1 = await DistributedRuntime.connect("127.0.0.1", server.port, "w1")
            rt2 = await DistributedRuntime.connect("127.0.0.1", server.port, "c1")
            ep = rt1.namespace("ns").component("echo").endpoint("generate")
            await ep.serve(echo_engine)
            client = rt2.namespace("ns").component("echo").endpoint(
                "generate").client()
            await client.start()
            await client.wait_for_instances()
            frames = [f async for f in await client.generate({"n": 2})]
            assert [f["i"] for f in frames] == [0, 1]
            await rt1.shutdown()
            await rt2.shutdown()
        finally:
            await server.stop()

    run(main())


def test_dataplane_uses_uds_same_host_and_tcp_when_disabled(monkeypatch):
    """SURVEY §2.1 alternative data plane (the reference's ZMQ/IPC
    option): same-host call-home streams ride the requester's advertised
    unix socket; DYN_DATAPLANE=tcp forces plain TCP."""
    async def roundtrip():
        plane = MemoryPlane()
        server_rt = await DistributedRuntime.create_local(plane, "w")
        client_rt = await DistributedRuntime.create_local(plane, "c")
        ep = server_rt.namespace("ns").component("e").endpoint("g")
        await ep.serve(echo_engine)
        client = client_rt.namespace("ns").component("e").endpoint(
            "g").client()
        await client.start()
        await client.wait_for_instances()
        frames = [f async for f in await client.generate({"n": 3})]
        dp = await client_rt.data_plane()
        stats = (dp.uds_accepts, dp.uds_path)
        await client_rt.shutdown()
        await server_rt.shutdown()
        assert [f["i"] for f in frames] == [0, 1, 2]
        return stats

    # default (auto): the stream arrives via the unix socket
    monkeypatch.delenv("DYN_DATAPLANE", raising=False)
    accepts, path = run(roundtrip())
    assert path is not None and accepts >= 1

    # forced TCP: no UDS listener, streaming still works
    monkeypatch.setenv("DYN_DATAPLANE", "tcp")
    accepts, path = run(roundtrip())
    assert path is None and accepts == 0


def test_served_endpoint_re_role_fence_and_role_routing():
    """ISSUE 12: the real-worker re-registration path. A live served
    instance re-roles decode->prefill through the DRAINING fence; the
    watching client's `ids_for_role` never lists it for the old role
    after the fence event applies, and lists it for the new role only
    after the ready re-put. Role-less instances stay wildcards."""
    async def main():
        plane = MemoryPlane()
        wrt = await DistributedRuntime.create_local(plane, "w-roled")
        art = await DistributedRuntime.create_local(plane, "w-any")
        crt = await DistributedRuntime.create_local(plane, "cl")
        ep = wrt.namespace("ns").component("gen").endpoint("generate")
        served = await ep.serve(echo_engine, metadata={"role": "decode"})
        await art.namespace("ns").component("gen").endpoint(
            "generate").serve(echo_engine)     # role-less wildcard
        client = crt.namespace("ns").component("gen").endpoint(
            "generate").client()
        await client.start()
        await client.wait_for_instances()

        async def wait_for(pred, timeout=5.0):
            deadline = asyncio.get_running_loop().time() + timeout
            while not pred():
                assert asyncio.get_running_loop().time() < deadline, \
                    "condition never held"
                await asyncio.sleep(0.01)

        await wait_for(lambda: "w-roled" in client.ids_for_role("decode"))
        # the role-less instance serves every role
        assert "w-any" in client.ids_for_role("decode")
        assert "w-any" in client.ids_for_role("prefill")
        assert "w-roled" not in client.ids_for_role("prefill")

        res = await served.re_role("prefill", drain_timeout_s=1.0)
        assert res["from_role"] == "decode" and res["to_role"] == "prefill"
        await wait_for(lambda: "w-roled" in client.ids_for_role("prefill"))
        assert "w-roled" not in client.ids_for_role("decode")
        assert "w-roled" not in client.draining_ids()
        # requests still route to the re-roled instance
        frames = [f async for f in await client.direct(
            {"n": 2, "text": "post-re-role"}, "w-roled")]
        assert [f["i"] for f in frames] == [0, 1]

        # mid-fence: a draining re-put removes it from BOTH role lists
        await served.mark_draining()
        await wait_for(
            lambda: "w-roled" not in client.ids_for_role("prefill"))
        assert "w-roled" not in client.ids_for_role("decode")
        assert "w-roled" in client.draining_ids()
        await crt.shutdown()
        await art.shutdown()
        await wrt.shutdown()

    run(main())
