"""Standalone metrics exporter tests (VERDICT r2 next #9).

The 'Done' bar: the exporter serves llm_kv_blocks_* for a 2-worker graph.
Reference: components/metrics binary, components/metrics/src/lib.rs:96-616.
"""
import asyncio

from dynamo_tpu.kv_router.publisher import KV_HIT_RATE_SUBJECT
from dynamo_tpu.observability.exporter import MetricsExporter
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.transports.memory import MemoryPlane


async def fake_engine(request, context):
    yield {"ok": True}


def test_exporter_two_worker_graph():
    async def main():
        plane = MemoryPlane()
        rts = []
        # w0 also reports decode-pipeline occupancy, through a mutable
        # dict so the test can advance it mid-run (what a live engine's
        # step loop does) and assert the gauges follow
        pipe_stats = {"decode_windows": 4, "pipeline_windows": 3,
                      "pipeline_overlapped": 2, "pipeline_fallbacks": 1,
                      "window_steps_reconciled": 8,
                      "window_steps_discarded": 0,
                      "decode_host_syncs": 4, "decode_plan_uploads": 1}
        for i, (active, total) in enumerate(((3, 16), (5, 16))):
            rt = await DistributedRuntime.create_local(plane, f"w{i}")
            ep = rt.namespace("ns").component("worker").endpoint("generate")
            extra = pipe_stats if i == 0 else {}
            await ep.serve(
                fake_engine,
                stats_handler=lambda a=active, t=total, e=extra: {
                    "request_active_slots": 1, "request_total_slots": 4,
                    "kv_active_blocks": a, "kv_total_blocks": t,
                    "num_requests_waiting": 0,
                    "gpu_cache_usage_perc": a / t,
                    "gpu_prefix_cache_hit_rate": 0.5, **e})
            rts.append(rt)

        ert = await DistributedRuntime.create_local(plane, "exporter")
        exporter = MetricsExporter(ert, "ns", "worker", port=0,
                                   scrape_interval_s=0.05)
        await exporter.start()
        try:
            # router hit-rate event rides the component event plane
            await rts[0].namespace("ns").component("router").publish(
                KV_HIT_RATE_SUBJECT,
                {"worker_id": "w0", "isl_blocks": 8, "overlap_blocks": 6})
            await asyncio.sleep(0.3)  # a few scrape cycles

            reader, writer = await asyncio.open_connection(
                "127.0.0.1", exporter.port)
            writer.write(b"GET /metrics HTTP/1.1\r\nhost: x\r\n\r\n")
            await writer.drain()
            raw = await reader.read(65536)
            writer.close()
            body = raw.decode()
            assert "200 OK" in body
            assert 'llm_kv_blocks_active{worker="w0"} 3' in body
            assert 'llm_kv_blocks_active{worker="w1"} 5' in body
            assert 'llm_kv_blocks_total{worker="w0"} 16' in body
            assert "llm_workers 2" in body
            assert "llm_load_avg 4" in body
            assert "llm_router_kv_hit_rate 0.75" in body
            # decode-pipeline occupancy gauges (overlap counters)
            assert 'llm_decode_windows{worker="w0"} 4' in body
            assert 'llm_decode_pipeline_overlapped{worker="w0"} 2' in body
            assert 'llm_decode_pipeline_fallbacks{worker="w0"} 1' in body
            # a follow-up committed after that fallback, none dropped
            assert 'llm_decode_window_steps_reconciled{worker="w0"} 8' \
                in body
            assert 'llm_decode_window_steps_discarded{worker="w0"} 0' \
                in body
            assert 'llm_decode_plan_uploads{worker="w0"} 1' in body
            # the engine keeps committing overlapped windows: the gauges
            # must ADVANCE with the next scrape
            pipe_stats.update(decode_windows=11, pipeline_windows=10,
                              pipeline_overlapped=9, decode_host_syncs=10,
                              pipeline_fallbacks=2,
                              window_steps_reconciled=16)

            # reliability counter snapshots ride the event plane the same
            # way ({ns}.{source}.reliability) and fold into gauges labeled
            # by the publishing frontend
            from dynamo_tpu.frontend.reliability import ReliabilityMetrics
            rm = ReliabilityMetrics()
            rm.migrations.inc(value=3)
            rm.retries.inc(value=2)
            rm.breaker_opens.inc()
            rm.shed_requests.inc(value=5)
            rm.stall_fires.inc()
            await rm.publish(rts[0].namespace("ns").component("front0"))
            await asyncio.sleep(0.2)

            # a worker going away drops its series
            await rts[1].shutdown()
            await asyncio.sleep(0.3)
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", exporter.port)
            writer.write(b"GET /metrics HTTP/1.1\r\n\r\n")
            await writer.drain()
            body2 = (await reader.read(65536)).decode()
            writer.close()
            assert 'llm_kv_blocks_active{worker="w1"}' not in body2
            assert "llm_workers 1" in body2
            assert 'llm_decode_windows{worker="w0"} 11' in body2
            assert 'llm_decode_pipeline_overlapped{worker="w0"} 9' in body2
            assert 'llm_decode_host_syncs{worker="w0"} 10' in body2
            assert 'llm_decode_window_steps_reconciled{worker="w0"} 16' \
                in body2
            assert 'llm_reliability_migrations{source="front0"} 3' in body2
            assert 'llm_reliability_retries{source="front0"} 2' in body2
            assert 'llm_reliability_breaker_opens{source="front0"} 1' \
                in body2
            assert 'llm_reliability_breaker_closes{source="front0"} 0' \
                in body2
            assert 'llm_reliability_shed_requests{source="front0"} 5' \
                in body2
            assert 'llm_reliability_stall_fires{source="front0"} 1' in body2
            assert 'llm_reliability_deadline_exceeded{source="front0"} 0' \
                in body2
            # control-plane gauges (runtime/cpstats.py CP_STATS), folded
            # at render: the exporter's own Client watch feeds them, and
            # a synthetic bump must be visible on the next scrape
            from dynamo_tpu.runtime.cpstats import CP_STATS
            assert "llm_cp_watch_queue_depth" in body2
            assert "llm_cp_router_degraded" in body2
            CP_STATS.indexer_nodes = 12345
            CP_STATS.router_degraded = 1
            CP_STATS.event_lag_seconds = 2.5
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", exporter.port)
            writer.write(b"GET /metrics HTTP/1.1\r\n\r\n")
            await writer.drain()
            body3 = (await reader.read(65536)).decode()
            writer.close()
            assert "llm_cp_indexer_nodes 12345" in body3
            assert "llm_cp_router_degraded 1" in body3
            assert "llm_cp_event_lag_seconds 2.5" in body3
            CP_STATS.reset()
        finally:
            await exporter.stop()
            for rt in rts:
                await rt.shutdown()
            await ert.shutdown()

    asyncio.run(main())


def _series_count(exporter) -> int:
    """Total live label series across every per-worker gauge family."""
    return sum(len(g._values) for g in exporter._worker_gauges())


def test_exporter_series_lifecycle_under_rolling_restart_churn():
    """Satellite (ISSUE 10): departed workers' per-instance series are
    remove()d at WATCH-EVENT time (the kv_router on_instance eviction,
    mirrored), so a rolling restart of uniquely-named workers cannot
    grow the exporter's series set without bound — and the eviction
    does NOT wait for the next scrape cycle."""
    async def main():
        plane = MemoryPlane()
        ert = await DistributedRuntime.create_local(plane, "exporter")
        # slow scrape interval: eviction must come from the watch path,
        # not from a lucky scrape landing in the sleep below
        exporter = MetricsExporter(ert, "ns", "worker", port=0,
                                   scrape_interval_s=30.0)
        await exporter.start()
        counts = []
        try:
            for gen in range(3):       # 3 generations of 2 workers each
                rts = []
                for i in range(2):
                    rt = await DistributedRuntime.create_local(
                        plane, f"gen{gen}-w{i}")
                    ep = rt.namespace("ns").component(
                        "worker").endpoint("generate")
                    await ep.serve(
                        fake_engine,
                        stats_handler=lambda: {
                            "request_active_slots": 1,
                            "request_total_slots": 4,
                            "kv_active_blocks": 2, "kv_total_blocks": 16,
                            "num_requests_waiting": 0,
                            "gpu_cache_usage_perc": 0.1,
                            "gpu_prefix_cache_hit_rate": 0.5})
                    rts.append(rt)
                await asyncio.sleep(0.05)      # watch puts land
                await exporter._aggregator.scrape_once()
                counts.append(_series_count(exporter))
                for rt in rts:                 # the whole generation dies
                    await rt.shutdown()
                await asyncio.sleep(0.05)      # watch DELETES land
                # no scrape between death and this check: the watch
                # listener alone must have evicted the series
                counts.append(_series_count(exporter))
            return counts
        finally:
            await exporter.stop()
            await ert.shutdown()

    counts = asyncio.run(main())
    alive, dead = counts[0::2], counts[1::2]
    # every generation renders the same bounded series count while
    # alive, and zero per-worker series after its delete events apply
    assert all(c == alive[0] > 0 for c in alive), counts
    assert all(c == 0 for c in dead), counts
