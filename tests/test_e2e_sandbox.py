"""E2E: sandbox depth — process manager, fs API, snapshots
(reference sdk sandbox.py:137,376,916 surface, redesigned over the state
bus: spawned procs are runtime PTY sessions whose output rides bus streams
the gateway reads directly)."""

import asyncio
import base64
import sys

import pytest

from tpu9.testing.localstack import LocalStack

pytestmark = pytest.mark.e2e


async def make_sandbox(stack) -> str:
    status, out = await stack.api("POST", "/rpc/stub/get-or-create", json_body={
        "name": "sbx", "stub_type": "sandbox",
        "config": {"runtime": {"cpu_millicores": 500, "memory_mb": 512}}})
    assert status == 200, out
    status, pod = await stack.api("POST", "/rpc/pod/create", json_body={
        "stub_id": out["stub_id"], "wait": True, "timeout": 30})
    assert status == 200 and pod.get("running"), pod
    return pod["container_id"]


async def read_out(stack, cid, proc_id, last_id="0", timeout=5):
    status, out = await stack.api(
        "GET", f"/rpc/pod/{cid}/proc/{proc_id}/out"
               f"?last_id={last_id}&timeout={timeout}")
    assert status == 200, out
    return out


async def test_process_manager_spawn_stream_stdin_kill():
    async with LocalStack() as stack:
        cid = await make_sandbox(stack)

        # spawn a long-running process that echoes stdin lines
        status, out = await stack.api(
            "POST", f"/rpc/pod/{cid}/proc",
            json_body={"cmd": ["/bin/sh", "-c",
                               "echo ready; while read l; do echo got:$l; "
                               "done"]})
        assert status == 200 and out.get("proc_id"), out
        proc_id = out["proc_id"]

        # it shows in ps and is running
        status, ps = await stack.api("GET", f"/rpc/pod/{cid}/proc")
        assert any(p["proc_id"] == proc_id and p["running"]
                   for p in ps["procs"]), ps

        # output streams: first line is "ready"
        chunk = await read_out(stack, cid, proc_id)
        text = base64.b64decode(chunk["data"]).decode()
        assert "ready" in text, text

        # stdin round-trip
        status, _ = await stack.api(
            "POST", f"/rpc/pod/{cid}/proc/{proc_id}/stdin",
            json_body={"data": base64.b64encode(b"hello\n").decode()})
        assert status == 200
        deadline = 20
        acc = ""
        last = chunk["last_id"]
        while "got:hello" not in acc and deadline > 0:
            chunk = await read_out(stack, cid, proc_id, last_id=last,
                                   timeout=2)
            last = chunk["last_id"]
            acc += base64.b64decode(chunk["data"]).decode()
            deadline -= 1
        assert "got:hello" in acc, acc

        # kill; status flips to exited
        status, _ = await stack.api(
            "POST", f"/rpc/pod/{cid}/proc/{proc_id}/kill")
        assert status == 200
        for _ in range(50):
            status, st = await stack.api(
                "GET", f"/rpc/pod/{cid}/proc/{proc_id}")
            if not st.get("running"):
                break
            await asyncio.sleep(0.1)
        assert not st.get("running"), st


async def test_fs_api_roundtrip():
    async with LocalStack() as stack:
        cid = await make_sandbox(stack)

        async def fs(op, path, data=b""):
            status, out = await stack.api(
                "POST", f"/rpc/pod/{cid}/fs",
                json_body={"op": op, "path": path,
                           "data": base64.b64encode(data).decode()
                           if data else ""})
            assert status == 200, out
            return out

        up = await fs("write", "sub/data.bin", b"\x00\x01payload")
        assert up.get("ok") and up["size"] == 9

        # the container actually sees the file (exec path agrees with fs path)
        status, out = await stack.api(
            "POST", f"/rpc/pod/{cid}/exec",
            json_body={"cmd": ["/bin/sh", "-c", "wc -c < sub/data.bin"]})
        assert out["exit_code"] == 0 and "9" in out["output"], out

        down = await fs("read", "sub/data.bin")
        assert base64.b64decode(down["data"]) == b"\x00\x01payload"

        ls = await fs("ls", "sub")
        assert [e["name"] for e in ls["entries"]] == ["data.bin"]
        st = await fs("stat", "sub/data.bin")
        assert st["size"] == 9 and not st["is_dir"]

        # containment: escaping paths are rejected
        esc = await fs("read", "../../../etc/passwd")
        assert esc.get("error"), esc

        rm = await fs("rm", "sub")
        assert rm.get("ok")
        gone = await fs("stat", "sub/data.bin")
        assert gone.get("error")


async def test_snapshot_and_restore_into_new_sandbox():
    async with LocalStack() as stack:
        cid = await make_sandbox(stack)
        status, out = await stack.api(
            "POST", f"/rpc/pod/{cid}/exec",
            json_body={"cmd": ["/bin/sh", "-c",
                               "echo persisted > keep.txt"]})
        assert out["exit_code"] == 0, out

        status, snap = await stack.api("POST", f"/rpc/pod/{cid}/snapshot")
        assert status == 200 and snap.get("snapshot_id"), snap
        assert snap["files"] >= 1

        # listed for the workspace
        status, snaps = await stack.api("GET", "/rpc/pod/snapshots")
        assert any(s["snapshot_id"] == snap["snapshot_id"] for s in snaps)

        # new sandbox from the snapshot sees the working tree
        status, out = await stack.api("POST", "/rpc/stub/get-or-create",
                                      json_body={
            "name": "sbx2", "stub_type": "sandbox",
            "config": {"runtime": {"cpu_millicores": 500, "memory_mb": 512}}})
        status, pod2 = await stack.api("POST", "/rpc/pod/create", json_body={
            "stub_id": out["stub_id"], "wait": True, "timeout": 30,
            "from_snapshot": snap["snapshot_id"]})
        assert status == 200 and pod2.get("running"), pod2
        status, out = await stack.api(
            "POST", f"/rpc/pod/{pod2['container_id']}/exec",
            json_body={"cmd": ["/bin/sh", "-c", "cat keep.txt"]})
        assert out["exit_code"] == 0 and "persisted" in out["output"], out

        # unknown/foreign snapshot id 404s
        status, _ = await stack.api("POST", "/rpc/pod/create", json_body={
            "stub_id": pod2["container_id"], "wait": False,
            "from_snapshot": "sbxsnap-doesnotexist"})
        assert status in (400, 404)


async def test_run_code_via_spawned_python():
    async with LocalStack() as stack:
        cid = await make_sandbox(stack)
        status, out = await stack.api(
            "POST", f"/rpc/pod/{cid}/proc",
            json_body={"cmd": [sys.executable, "-u", "-c",
                               "print(sum(range(10)))"]})
        proc_id = out["proc_id"]
        acc, last = "", "0"
        for _ in range(40):
            chunk = await read_out(stack, cid, proc_id, last_id=last,
                                   timeout=2)
            last = chunk["last_id"]
            acc += base64.b64decode(chunk["data"]).decode()
            if chunk.get("exit_code") is not None:
                break
        assert "45" in acc, acc
        assert chunk["exit_code"] == 0


async def test_t9proc_is_pid1_and_reaps_zombies(built):
    """VERDICT r03 #7 'Done' criteria: sandbox processes run under the
    t9proc supervisor (not nsenter-style exec) and orphaned children are
    reaped — no zombies accumulate under the container's init."""
    import base64
    import os
    import shutil

    async with LocalStack() as stack:
        cid = await make_sandbox(stack)

        # the supervisor socket exists in the sandbox workdir → the agent
        # routes through t9proc, and the worker-side client is live
        worker = next(w for w in stack.workers
                      if w.runtime.fs_root(cid))
        root = worker.runtime.fs_root(cid)
        assert os.path.exists(os.path.join(root, ".t9proc.sock"))

        # orphan-maker: the child double-forks; the grandchild outlives it
        # and reparents to PID 1 (t9proc) which must reap it on exit
        status, out = await stack.api(
            "POST", f"/rpc/pod/{cid}/proc",
            json_body={"cmd": ["/bin/sh", "-c",
                               "(sleep 0.2 &) ; echo spawned-orphan"]})
        assert status == 200, out
        got = await read_out(stack, cid, out["proc_id"])
        text = base64.b64decode(got.get("data", "")).decode()
        assert "spawned-orphan" in text

        assert worker.sandboxes._t9proc.get(cid) is not None, \
            "agent did not route through the PID-1 supervisor"

        # give the orphan time to die, then prove zero zombies among
        # t9proc's children (host view: find the supervisor pid and check
        # its children's states)
        await asyncio.sleep(0.6)
        handle = await worker.runtime.state(cid)
        zombies = []
        for pid_dir in os.listdir("/proc"):
            if not pid_dir.isdigit():
                continue
            try:
                with open(f"/proc/{pid_dir}/stat") as f:
                    parts = f.read().split()
                if parts[3] == str(handle.pid) and parts[2] == "Z":
                    zombies.append(pid_dir)
            except OSError:
                continue
        assert zombies == [], f"unreaped zombies under t9proc: {zombies}"

        # stdin + exit codes flow through the supervised path too
        status, out = await stack.api(
            "POST", f"/rpc/pod/{cid}/proc",
            json_body={"cmd": ["/bin/sh", "-c",
                               "read x; echo got:$x; exit 3"]})
        proc_id = out["proc_id"]
        status, _ = await stack.api(
            "POST", f"/rpc/pod/{cid}/proc/{proc_id}/stdin",
            json_body={"data": base64.b64encode(b"ping\n").decode()})
        assert status == 200
        got = await read_out(stack, cid, proc_id)
        text = base64.b64decode(got.get("data", "")).decode()
        assert "got:ping" in text
        st = {}
        for _ in range(100):              # exit event is asynchronous
            status, st = await stack.api(
                "GET", f"/rpc/pod/{cid}/proc/{proc_id}")
            if st.get("exit_code") is not None:
                break
            await asyncio.sleep(0.05)
        assert st.get("exit_code") == 3, st
