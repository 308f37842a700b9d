"""Runtime composition root + CLI surface."""

import asyncio
import json

from quoracle_tpu.models.runtime import MockBackend
from quoracle_tpu.runtime import Runtime, RuntimeConfig

POOL = MockBackend.DEFAULT_POOL


def j(action, params=None, wait=False):
    return json.dumps({"action": action, "params": params or {},
                       "reasoning": "t", "wait": wait})


def test_runtime_full_stack_create_pause_reboot(tmp_path):
    db_path = str(tmp_path / "q.db")

    async def phase1():
        rt = Runtime(RuntimeConfig(db_path=db_path, encryption_key="k"),
                     backend=MockBackend(respond=lambda r: j("wait", {})))
        task_id, root = await rt.tasks.create_task("hold", model_pool=list(POOL))
        for _ in range(200):
            await asyncio.sleep(0.02)
            if len(root.ctx.history(POOL[0])) >= 3:
                break
        await rt.tasks.pause_task(task_id)
        assert rt.status()["tasks"][task_id] == "paused"
        # simulate crash-while-running for revival
        rt.store.db.execute("UPDATE tasks SET status='running' WHERE id=?",
                            (task_id,))
        rt.close()
        return task_id

    async def phase2(task_id):
        rt = Runtime(RuntimeConfig(db_path=db_path, encryption_key="k"),
                     backend=MockBackend(respond=lambda r: j("wait", {})))
        result = await rt.boot()
        assert result["revived"] == [task_id]
        assert len(rt.registry) == 1
        await rt.shutdown()

    task_id = asyncio.run(asyncio.wait_for(phase1(), 60))
    asyncio.run(asyncio.wait_for(phase2(task_id), 60))


def test_runtime_isolation():
    # two runtimes share nothing (the cardinal DI rule)
    rt1 = Runtime(backend=MockBackend())
    rt2 = Runtime(backend=MockBackend())
    assert rt1.registry is not rt2.registry
    assert rt1.bus is not rt2.bus
    assert rt1.escrow is not rt2.escrow
    rt1.secrets.put("only-in-1", "value-123")
    assert rt2.secrets.lookup("only-in-1") is None
    rt1.close()
    rt2.close()


def test_cli_run_and_status(tmp_path, capsys):
    from quoracle_tpu.cli import main
    db_path = str(tmp_path / "cli.db")
    rc = main(["run", "do nothing much", "--db", db_path,
               "--watch-seconds", "1.5"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "task task-" in out
    assert "spawned" in out
    rc = main(["status", "--db", db_path])
    assert rc == 0
    out = capsys.readouterr().out
    status = json.loads(out)
    assert list(status["tasks"].values()) == ["paused"]


def test_checkpoints_require_tpu_backend():
    """--checkpoint on the default mock backend must fail loudly, not
    silently serve scripted responses (review r3 finding)."""
    import pytest
    from quoracle_tpu.runtime import Runtime, RuntimeConfig
    with pytest.raises(ValueError, match="require --backend tpu"):
        Runtime(RuntimeConfig(checkpoints=["/nonexistent"]))


def test_cluster_flags_require_tpu_backend():
    """--coordinator/--num-processes/--process-id on the mock backend must
    fail loudly — a user who believes they launched a multi-host run must
    not get scripted mock responses (same rule as --checkpoint)."""
    import pytest
    from quoracle_tpu.runtime import Runtime, RuntimeConfig
    for kw in ({"coordinator_address": "h:1"}, {"num_processes": 2},
               {"process_id": 0}):
        with pytest.raises(ValueError, match="require --backend tpu"):
            Runtime(RuntimeConfig(**kw))


def test_continuous_flag_is_accepted_and_read_nowhere(monkeypatch):
    """``serve --continuous`` still parses (benchmark/run.py builds it into
    every cell's argv) and changes nothing: the runtime an argv describes
    is the same with the flag and without it, and no option of the
    runtime's is left for it to set."""
    import dataclasses

    from quoracle_tpu import cli

    monkeypatch.setattr(cli, "Runtime", lambda config: config)
    base = ["serve", "--backend", "tpu", "--pool", "xla:tiny", "--port", "0"]
    parse = cli.build_parser().parse_args
    with_flag = cli.runtime_from_args(
        parse(base[:3] + ["--continuous"] + base[3:]))
    without = cli.runtime_from_args(parse(base))
    assert isinstance(without, RuntimeConfig)
    assert with_flag == without
    assert "continuous" not in {f.name
                                for f in dataclasses.fields(RuntimeConfig)}
