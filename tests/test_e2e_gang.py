"""E2E: gang scheduling a multi-host TPU slice with real runner containers.

A v5p-8 request (2 hosts × 4 chips) must atomically land one container on
each host of a virtual slice, with rank/coordinator env wired the way
jax.distributed consumes it (SURVEY.md §2.10)."""

import asyncio

import pytest

from tpu9.testing.localstack import LocalStack
from tpu9.types import ContainerRequest, parse_tpu_spec

pytestmark = pytest.mark.e2e

GANG_HANDLER = """
import os

def handler(**kw):
    return {
        "rank": os.environ.get("TPU9_GANG_RANK"),
        "size": os.environ.get("TPU9_GANG_SIZE"),
        "coord": os.environ.get("TPU9_COORDINATOR_ADDR"),
        "tpu_worker_id": os.environ.get("TPU_WORKER_ID"),
        "chips": os.environ.get("TPU_VISIBLE_CHIPS"),
        "accel": os.environ.get("TPU_ACCELERATOR_TYPE"),
    }
"""


async def test_gang_containers_run_with_rank_env():
    async with LocalStack() as stack:
        # two virtual v5p hosts sharing one slice
        for rank in range(2):
            await stack._worker_factory(
                tpu_chips=4, tpu_generation="v5p", slice_id="slice-A",
                slice_topology="2x2x2", slice_host_rank=rank,
                slice_host_count=2)

        object_id = await stack.upload_workspace({"app.py": GANG_HANDLER})
        status, out = await stack.api("POST", "/rpc/stub/get-or-create",
                                      json_body={
            "name": "gangfn", "stub_type": "endpoint",
            "config": {"handler": "app:handler", "keep_warm_seconds": 5.0,
                       "runtime": {"tpu": "v5p-8", "cpu_millicores": 500,
                                   "memory_mb": 512}},
            "object_id": object_id})
        stub_id = out["stub_id"]

        # drive the scheduler directly with a gang request (endpoint
        # autoscaling of gangs rides the same path)
        req = ContainerRequest(
            stub_id=stub_id,
            workspace_id=stack.gateway.default_workspace.workspace_id,
            stub_type="endpoint", cpu_millicores=500, memory_mb=512,
            tpu="v5p-8", object_id=object_id,
            env={"TPU9_HANDLER": "app:handler", "TPU9_STUB_TYPE": "endpoint",
                 "TPU9_CONCURRENT_REQUESTS": "1", "TPU9_WORKERS": "1",
                 "TPU9_TIMEOUT_S": "60"})
        await stack.gateway.scheduler.run(req)

        # both gang members must reach RUNNING
        await stack.wait_running(stub_id, n=2, timeout=60)
        states = await stack.running_containers(stub_id)
        assert len(states) == 2
        gang_ids = {s.gang_id for s in states}
        assert len(gang_ids) == 1 and "" not in gang_ids

        # ask each container for its env through its own server
        import aiohttp
        results = []
        async with aiohttp.ClientSession() as session:
            for s in states:
                async with session.post(f"http://{s.address}/",
                                        json={}) as resp:
                    assert resp.status == 200
                    results.append(await resp.json())
        ranks = sorted(r["rank"] for r in results)
        assert ranks == ["0", "1"]
        assert all(r["size"] == "2" for r in results)
        coords = {r["coord"] for r in results}
        assert len(coords) == 1 and list(coords)[0]
        assert all(r["chips"] == "0,1,2,3" for r in results)
        # tpu9's "v5p-8" (8 chips) in the platform's spelling (16 cores)
        assert all(r["accel"] == "v5p-16" for r in results)
        assert sorted(r["tpu_worker_id"] for r in results) == ["0", "1"]

        # chips are reserved on both hosts while the gang runs
        workers = await stack.gateway.workers.list()
        slice_members = [w for w in workers if w.slice_id == "slice-A"]
        assert all(w.tpu_free_chips == 0 for w in slice_members)


async def test_gang_member_failure_shares_fate():
    async with LocalStack() as stack:
        for rank in range(2):
            await stack._worker_factory(
                tpu_chips=4, tpu_generation="v5p", slice_id="slice-B",
                slice_topology="2x2x2", slice_host_rank=rank,
                slice_host_count=2)
        object_id = await stack.upload_workspace({"app.py": GANG_HANDLER})
        _, out = await stack.api("POST", "/rpc/stub/get-or-create", json_body={
            "name": "gang2", "stub_type": "endpoint",
            "config": {"handler": "app:handler",
                       "runtime": {"tpu": "v5p-8", "cpu_millicores": 500,
                                   "memory_mb": 512}},
            "object_id": object_id})
        stub_id = out["stub_id"]
        req = ContainerRequest(
            stub_id=stub_id,
            workspace_id=stack.gateway.default_workspace.workspace_id,
            stub_type="endpoint", cpu_millicores=500, memory_mb=512,
            tpu="v5p-8", object_id=object_id,
            env={"TPU9_HANDLER": "app:handler", "TPU9_STUB_TYPE": "endpoint",
                 "TPU9_CONCURRENT_REQUESTS": "1", "TPU9_WORKERS": "1",
                 "TPU9_TIMEOUT_S": "60"})
        await stack.gateway.scheduler.run(req)
        await stack.wait_running(stub_id, n=2, timeout=60)
        states = await stack.running_containers(stub_id)

        # kill one member's worker (simulated host loss) and run the pool
        # monitor's reap — the peer must be stopped too (shared fate)
        victim = states[0]
        victim_worker = next(w for w in stack.workers
                             if w.worker_id == victim.worker_id)
        # stop heartbeats without a clean drain
        for t in victim_worker._tasks:
            t.cancel()
        await stack.store.delete(
            f"worker:keepalive:{victim_worker.worker_id}")

        from tpu9.scheduler.pool_health import PoolMonitor
        from tpu9.config import WorkerPoolConfig
        mon = PoolMonitor(stack.store, {}, {"default": WorkerPoolConfig()})
        await mon.tick()

        # the surviving peer should be told to stop
        for _ in range(100):
            left = await stack.running_containers(stub_id)
            if len(left) == 0:
                break
            await asyncio.sleep(0.1)
        assert len(await stack.running_containers(stub_id)) == 0