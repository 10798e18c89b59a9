"""Train library tests: the minimum end-to-end slice (SURVEY.md §7 phase 7)
— trainer → placement group → worker actors → collective DP → session
reports → checkpoints → resume → elastic restart."""

import os

import numpy as np
import pytest

import ray_memory_management_tpu as rmt
from ray_memory_management_tpu.train import (
    Checkpoint,
    FailureConfig,
    JaxTrainer,
    Result,
    RunConfig,
    ScalingConfig,
)


# ------------------------------------------------------------- checkpoint
def test_checkpoint_dict_bytes_roundtrip(tmp_path):
    ck = Checkpoint.from_dict({"a": 1, "arr": np.arange(5)})
    d = Checkpoint.from_bytes(ck.to_bytes()).to_dict()
    assert d["a"] == 1 and np.array_equal(d["arr"], np.arange(5))


def test_checkpoint_directory_roundtrip(tmp_path):
    ck = Checkpoint.from_dict({"a": [1, 2]})
    path = ck.to_directory(str(tmp_path / "c1"))
    d = Checkpoint.from_directory(path).to_dict()
    assert d["a"] == [1, 2]


def test_checkpoint_pytree_orbax_roundtrip(tmp_path):
    import jax.numpy as jnp

    tree = {"w": jnp.ones((4, 4)), "b": jnp.zeros(4)}
    ck = Checkpoint.from_pytree(tree, extra={"step": 3})
    path = ck.to_directory(str(tmp_path / "c2"))
    restored = Checkpoint.from_directory(path)
    out = restored.get_pytree()
    assert np.array_equal(np.asarray(out["w"]), np.ones((4, 4)))
    assert restored.to_dict()["step"] == 3


# ----------------------------------------------------------------- trainer
def _simple_loop(config):
    from ray_memory_management_tpu.train import Checkpoint, session

    rank = session.get_world_rank()
    start = 0
    ck = session.get_checkpoint()
    if ck is not None:
        start = ck.to_dict()["step"] + 1
    for step in range(start, config["steps"]):
        session.report(
            {"step": step, "rank": rank},
            checkpoint=Checkpoint.from_dict({"step": step})
            if rank == 0 else None,
        )


def test_fit_two_workers(rmt_start_regular, tmp_path):
    trainer = JaxTrainer(
        _simple_loop, train_loop_config={"steps": 4},
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(name="t1", storage_path=str(tmp_path)),
    )
    res = trainer.fit()
    assert res.error is None
    assert res.metrics["step"] == 3
    assert [m["step"] for m in res.metrics_history] == [0, 1, 2, 3]
    assert res.checkpoint.to_dict()["step"] == 3
    assert os.path.isdir(os.path.join(str(tmp_path), "t1"))


def test_fit_resume(rmt_start_regular, tmp_path):
    t1 = JaxTrainer(
        _simple_loop, train_loop_config={"steps": 3},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="r1", storage_path=str(tmp_path)),
    )
    r1 = t1.fit()
    t2 = JaxTrainer(
        _simple_loop, train_loop_config={"steps": 6},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="r2", storage_path=str(tmp_path)),
        resume_from_checkpoint=r1.checkpoint,
    )
    r2 = t2.fit()
    assert [m["step"] for m in r2.metrics_history] == [3, 4, 5]


def _collective_dp_loop(config):
    """Real distributed data-parallel: per-rank gradients allreduced through
    the worker group's collective."""
    import numpy as np

    from ray_memory_management_tpu import collective as col
    from ray_memory_management_tpu.train import session

    rank = session.get_world_rank()
    world = session.get_world_size()
    group = session.get_collective_group_name()
    w = np.zeros(2, np.float32)
    for step in range(config["steps"]):
        grad = np.full(2, float(rank + 1), np.float32)
        g = col.allreduce(grad, group_name=group) / world
        w = w - 0.1 * g
        session.report({"step": step, "w0": float(w[0])})


def test_fit_with_collective_allreduce(rmt_start_regular, tmp_path):
    trainer = JaxTrainer(
        _collective_dp_loop, train_loop_config={"steps": 3},
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(name="dp", storage_path=str(tmp_path)),
    )
    res = trainer.fit()
    assert res.error is None
    # mean grad = (1+2)/2 = 1.5 -> after 3 steps w0 = -0.45
    assert abs(res.metrics["w0"] + 0.45) < 1e-5


def _failing_loop(config):
    import os

    from ray_memory_management_tpu.train import Checkpoint, session

    marker = config["marker"]
    start = 0
    ck = session.get_checkpoint()
    if ck is not None:
        start = ck.to_dict()["step"] + 1
    for step in range(start, config["steps"]):
        if step == 2 and not os.path.exists(marker):
            open(marker, "w").write("crashed")
            os._exit(1)  # hard worker death mid-training
        session.report(
            {"step": step},
            checkpoint=Checkpoint.from_dict({"step": step})
            if session.get_world_rank() == 0 else None,
        )


def test_elastic_restart_from_checkpoint(rmt_start_regular, tmp_path):
    marker = str(tmp_path / "crashed_once")
    trainer = JaxTrainer(
        _failing_loop,
        train_loop_config={"steps": 5, "marker": marker},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(
            name="ft", storage_path=str(tmp_path),
            failure_config=FailureConfig(max_failures=1),
        ),
    )
    res = trainer.fit()
    assert res.error is None
    steps = [m["step"] for m in res.metrics_history]
    # crashed at step 2 (after reporting 0,1), restarted from ckpt step 1
    assert steps == [0, 1, 2, 3, 4]
    assert os.path.exists(marker)


def test_model_training_through_trainer(rmt_start_regular, tmp_path):
    """The flagship slice: TransformerLM trained through the Trainer."""

    def lm_loop(config):
        import jax
        import jax.numpy as jnp
        import optax

        from ray_memory_management_tpu.models import gpt
        from ray_memory_management_tpu.train import Checkpoint, session

        jax.config.update("jax_default_device", jax.devices("cpu")[0])
        cfg = gpt.PRESETS["test"]
        params = gpt.init_params(jax.random.PRNGKey(0), cfg)
        opt = optax.adam(1e-3)
        state = opt.init(params)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                  cfg.vocab_size)
        batch = {"tokens": toks, "targets": jnp.roll(toks, -1, 1)}

        @jax.jit
        def step(p, s):
            loss, g = jax.value_and_grad(
                lambda p_: gpt.loss_fn(p_, batch, cfg))(p)
            u, s = opt.update(g, s, p)
            return jax.tree.map(lambda a, b: a + b, p, u), s, loss

        for i in range(config["steps"]):
            params, state, loss = step(params, state)
            session.report({"step": i, "loss": float(loss)})
        session.report(
            {"final": True},
            checkpoint=Checkpoint.from_pytree(
                jax.tree.map(lambda x: np.asarray(x), params)),
        )

    trainer = JaxTrainer(
        lm_loop, train_loop_config={"steps": 3},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="lm", storage_path=str(tmp_path)),
    )
    res = trainer.fit()
    assert res.error is None
    losses = [m["loss"] for m in res.metrics_history if "loss" in m]
    assert losses[-1] < losses[0]
    assert res.checkpoint.get_pytree() is not None


def test_xla_cross_worker_global_mesh(rmt_start_regular, tmp_path):
    """Two worker PROCESSES form one jax.distributed world; the train step
    jits over the single global mesh, and the data-parallel gradient matches
    the single-process full-batch gradient (VERDICT r1 item 6; the
    _setup_torch_process_group analog, train/torch/config.py:54)."""
    import numpy as np

    from ray_memory_management_tpu.train import (
        JaxTrainer, RunConfig, ScalingConfig,
    )

    def loop():
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from ray_memory_management_tpu.train import session

        devs = jax.devices()  # GLOBAL devices across both worker processes
        n = len(devs)
        mesh = Mesh(np.array(devs), ("dp",))
        L = len(jax.local_devices())
        rank = jax.process_index()
        # one data point per global device: x_i = i + 1
        local = np.arange(rank * L + 1, rank * L + L + 1, dtype=np.float32)
        x = jax.make_array_from_process_local_data(
            NamedSharding(mesh, P("dp")), local)

        def loss(w, x):
            return jnp.mean((w * x - 1.0) ** 2)

        g = jax.jit(jax.grad(loss),
                    out_shardings=NamedSharding(mesh, P()))(
            jnp.float32(2.0), x)
        session.report({"grad": float(g), "n": n,
                        "processes": jax.process_count()})

    trainer = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=2,
                                     collective_backend="xla"),
        run_config=RunConfig(storage_path=str(tmp_path)),
    )
    res = trainer.fit()
    assert res.error is None
    reports = [m for m in res.metrics_history if "grad" in m]
    assert reports, "no gradient reported"
    rep = reports[-1]
    assert rep["processes"] == 2  # a real multi-process world formed
    full_x = np.arange(1, rep["n"] + 1, dtype=np.float32)
    expected = float(np.mean(2.0 * (2.0 * full_x - 1.0) * full_x))
    np.testing.assert_allclose(rep["grad"], expected, rtol=1e-5)


def test_xla_world_across_two_agent_nodes(tmp_path):
    """The global-mesh xla train runs with its two worker processes on two
    AGENT nodes (separate OS processes joined over TCP), not bare local
    actors — the gradient must still match the full-batch value
    (VERDICT r2 item 7, second half)."""
    import numpy as np

    from ray_memory_management_tpu.train import (
        JaxTrainer, RunConfig, ScalingConfig,
    )

    rt = rmt.init(num_cpus=0)  # head schedules nothing: workers go to agents
    try:
        node_a = rt.add_remote_node_process(num_cpus=2)
        node_b = rt.add_remote_node_process(num_cpus=2)

        def loop():
            import os

            import jax
            import jax.numpy as jnp
            import numpy as np
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

            from ray_memory_management_tpu.train import session

            devs = jax.devices()
            mesh = Mesh(np.array(devs), ("dp",))
            L = len(jax.local_devices())
            rank = jax.process_index()
            local = np.arange(rank * L + 1, rank * L + L + 1,
                              dtype=np.float32)
            x = jax.make_array_from_process_local_data(
                NamedSharding(mesh, P("dp")), local)

            def loss(w, x):
                return jnp.mean((w * x - 1.0) ** 2)

            g = jax.jit(jax.grad(loss),
                        out_shardings=NamedSharding(mesh, P()))(
                jnp.float32(2.0), x)
            session.report({
                "grad": float(g), "n": len(devs),
                "processes": jax.process_count(),
                "node": os.environ.get("RMT_NODE_ID", ""),
            })

        trainer = JaxTrainer(
            loop,
            scaling_config=ScalingConfig(
                num_workers=2, collective_backend="xla",
                placement_strategy="STRICT_SPREAD"),
            run_config=RunConfig(storage_path=str(tmp_path)),
        )
        res = trainer.fit()
        assert res.error is None, res.error
        reports = [m for m in res.metrics_history if "grad" in m]
        assert reports
        rep = reports[-1]
        assert rep["processes"] == 2
        # the two ranks really ran on the two agent NODES
        nodes = {m["node"] for m in reports if "node" in m}
        assert nodes <= {node_a.hex(), node_b.hex()}
        full_x = np.arange(1, rep["n"] + 1, dtype=np.float32)
        expected = float(np.mean(2.0 * (2.0 * full_x - 1.0) * full_x))
        np.testing.assert_allclose(rep["grad"], expected, rtol=1e-5)
    finally:
        rmt.shutdown()
