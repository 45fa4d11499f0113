"""E2E: the vcache LD_PRELOAD shim accelerates volume reads inside real
containers (node-cache copy wins over the volume path)."""

import os

import pytest

from tpu9.testing.localstack import LocalStack

pytestmark = pytest.mark.e2e

READER = """
import os
def handler(path="", **kw):
    with open(path) as f:
        return {"content": f.read().strip(),
                "preload": "vcache" in os.environ.get("LD_PRELOAD", "")}
"""


async def test_volume_reads_hit_node_cache(built):
    async with LocalStack() as stack:
        stack.cfg.worker.vcache_so = os.path.join(built, "vcache_preload.so")
        stack.cfg.worker.vcache_dir = os.path.join(stack.tmp.name, "vcache")

        ws = stack.gateway.default_workspace.workspace_id
        # volume file (source of truth) + a different cached copy
        status, _ = await stack.api("PUT", "/rpc/volume/models/files/w.txt",
                                    data=b"from-volume")
        assert status == 200
        cache_dir = os.path.join(stack.cfg.worker.vcache_dir, ws, "models")
        os.makedirs(cache_dir, exist_ok=True)
        with open(os.path.join(cache_dir, "w.txt"), "w") as f:
            f.write("from-node-cache")

        dep = await stack.deploy_endpoint(
            "vc", {"app.py": READER}, "app:handler",
            config_extra={"volumes": [{"name": "models",
                                       "mount_path": "/models"}]})
        # container reads its mounted volume path; shim redirects to cache
        out = await stack.invoke(dep, {"path": "models/w.txt"})
        # relative path → bypasses the shim prefix match → volume content
        assert out["content"] == "from-volume"
        assert out["preload"] is True

        # absolute container path → shim prefix matches → cached copy
        states = await stack.running_containers(dep["stub_id"])
        workdir = os.path.join(stack.cfg.worker.containers_dir,
                               states[0].container_id, "workspace")
        out2 = await stack.invoke(
            dep, {"path": os.path.join(workdir, "models", "w.txt")})
        assert out2["content"] == "from-node-cache"

        # uncached file under the same volume falls through to the volume
        status, _ = await stack.api("PUT",
                                    "/rpc/volume/models/files/only.txt",
                                    data=b"volume-only")
        out3 = await stack.invoke(
            dep, {"path": os.path.join(workdir, "models", "only.txt")})
        assert out3["content"] == "volume-only"
