"""The one compile-cache rule (ISSUE 21 §6): ``JAX_COMPILATION_CACHE_DIR``
when the environment sets it, else ``<checkout>/.cache/xla`` — and that, not
a path derived from a temp dir, is what every container is handed (the path
is part of the cache key: a directory that moves never hits)."""

import os

import pytest

from tpu9.testing.localstack import LocalStack
from tpu9.types import ContainerRequest, StubType
from tpu9.utils import compile_cache_dir, repo_root


def _container_cache_dir(stack: LocalStack, env: dict) -> str:
    """What the worker's lifecycle puts into a container's env."""
    from tpu9.repository import ContainerRepository
    from tpu9.runtime import ProcessRuntime
    from tpu9.worker.lifecycle import ContainerLifecycle
    from tpu9.worker.tpu_manager import TpuDeviceManager
    cfg = stack.cfg.worker
    life = ContainerLifecycle(
        "w1", cfg, ProcessRuntime(base_dir=cfg.containers_dir),
        ContainerRepository(stack.store), TpuDeviceManager())
    spec = life._spec_from_request(
        ContainerRequest(container_id="c1", env=env,
                         stub_type=StubType.ENDPOINT.value),
        rootfs="", workdir="/tmp", port=1, assignment=None)
    return spec.env["JAX_COMPILATION_CACHE_DIR"]


@pytest.mark.parametrize("set_to", ["", "/some/dir"])
def test_containers_get_the_one_cache_dir(monkeypatch, set_to):
    monkeypatch.setenv("TPU9_ZYGOTE", "0")
    if set_to:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", set_to)
        want = set_to
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(repo_root(), ".cache", "xla")
    assert compile_cache_dir() == want
    # two stacks, two TemporaryDirectory roots — the same cache path
    a, b = LocalStack(), LocalStack()
    try:
        assert a.cfg.worker.containers_dir != b.cfg.worker.containers_dir
        assert _container_cache_dir(a, {}) == want
        assert _container_cache_dir(b, {}) == want
    finally:
        a.tmp.cleanup()
        b.tmp.cleanup()


def test_the_default_cache_dir_is_git_ignored():
    with open(os.path.join(repo_root(), ".gitignore")) as f:
        assert ".cache/" in f.read().split()
