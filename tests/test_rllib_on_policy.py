"""On-policy RLlib-slim tests: PPO, IMPALA/V-trace, APPO, PG/A2C,
multi-agent, connectors, recurrent PPO and Tune integration (learning
regressions in the reference's tuned_examples reward-threshold style,
scaled to CI budgets, and checkpoint round-trips)."""

import numpy as np
import pytest

from ray_memory_management_tpu.rllib import IMPALAConfig, PPOConfig
from ray_memory_management_tpu.rllib import sample_batch as sb


class TestPPO:
    def test_donated_learner_step_compiles(self):
        """The TPU-learner bench path (utils/tpu_bench.rl_learner_bench)
        updates params/opt-state with donated buffers; pin that the
        donated update jit-compiles and matches the undonated one.
        Reference intent: the learner thread off the rollout path
        (rllib/execution/multi_gpu_learner_thread.py)."""
        import jax
        import jax.numpy as jnp
        import optax

        from ray_memory_management_tpu.rllib.models import ac_init
        from ray_memory_management_tpu.rllib.ppo import make_ppo_update

        opt = optax.adam(1e-3)
        key = jax.random.PRNGKey(0)
        params = ac_init(key, obs_dim=4, num_actions=2)
        params2 = jax.tree_util.tree_map(jnp.copy, params)
        state, state2 = opt.init(params), opt.init(params2)
        n = 32
        obs = jax.random.normal(key, (n, 4))
        actions = jnp.zeros((n,), jnp.int32)
        old_logp = jnp.full((n,), -0.69)
        adv = jax.random.normal(jax.random.PRNGKey(1), (n,))
        targets = jax.random.normal(jax.random.PRNGKey(2), (n,))

        upd = make_ppo_update(opt, 0.2, 0.5, 0.01, donate=False)
        upd_don = make_ppo_update(opt, 0.2, 0.5, 0.01, donate=True)
        p1, s1, st1 = upd(params, state, obs, actions, old_logp, adv,
                          targets)
        p2, s2, st2 = upd_don(params2, state2, obs, actions, old_logp,
                              adv, targets)
        for a, b in zip(jax.tree_util.tree_leaves(p1),
                        jax.tree_util.tree_leaves(p2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5)
        assert float(st2["total_loss"]) == pytest.approx(
            float(st1["total_loss"]), rel=1e-5)

    def test_rl_learner_bench_smoke(self):
        """The bench row itself (tiny sizes, CPU backend): full stack
        init -> rollout -> donated learner updates -> stats shape."""
        from ray_memory_management_tpu.utils.tpu_bench import (
            rl_learner_bench,
        )

        r = rl_learner_bench(n_workers=0, iters=1, train_batch=256,
                             fragment=128, num_sgd_iter=2, minibatch=128)
        assert r["env_steps_per_s"] > 0
        assert r["learner_env_steps_per_s"] >= r["env_steps_per_s"]
        assert r["learner_ms"] > 0
        assert r["algo"] == "ppo"

    def test_learns_cartpole(self):
        algo = (PPOConfig()
                .environment("CartPole",
                             env_config={"max_episode_steps": 200})
                .rollouts(num_rollout_workers=0,
                          rollout_fragment_length=400)
                .training(train_batch_size=1600, lr=3e-3, num_sgd_iter=8,
                          sgd_minibatch_size=256)
                .debugging(seed=1)
                .build())
        first = None
        result = {}
        for _ in range(8):
            result = algo.train()
            if first is None:
                first = result["episode_reward_mean"]
        assert result["episode_reward_mean"] > max(2 * first, 50)
        assert result["training_iteration"] == 8
        assert result["timesteps_total"] >= 8 * 1600
        algo.stop()

    def test_remote_workers(self, rmt_start_regular):
        algo = (PPOConfig()
                .environment("CartPole",
                             env_config={"max_episode_steps": 100})
                .rollouts(num_rollout_workers=2,
                          rollout_fragment_length=100)
                .training(train_batch_size=400)
                .debugging(seed=0)
                .build())
        r = algo.train()
        assert r["num_env_steps_sampled"] >= 400
        assert r["episodes_total"] > 0
        algo.stop()

    def test_checkpoint_roundtrip(self):
        cfg = (PPOConfig()
               .environment("CartPole",
                            env_config={"max_episode_steps": 100})
               .rollouts(num_rollout_workers=0,
                         rollout_fragment_length=100)
               .training(train_batch_size=200)
               .debugging(seed=2))
        algo = cfg.build()
        algo.train()
        blob = algo.save()
        obs = np.array([0.01, 0.0, 0.02, 0.0], dtype=np.float32)
        action_before = algo.compute_single_action(obs)
        w_before = algo.get_weights()
        algo2 = cfg.build()
        algo2.restore(blob)
        assert algo2.compute_single_action(obs) == action_before
        w_after = algo2.get_weights()
        np.testing.assert_allclose(
            w_before["pi"][0]["w"], w_after["pi"][0]["w"])
        assert algo2.iteration == 1
        algo.stop()
        algo2.stop()


class TestIMPALA:
    def test_learns_async(self, rmt_start_regular):
        algo = (IMPALAConfig()
                .environment("CartPole",
                             env_config={"max_episode_steps": 200})
                .rollouts(num_rollout_workers=2,
                          rollout_fragment_length=200)
                .training(train_batch_size=1600, lr=1e-3)
                .debugging(seed=1)
                .build())
        first = None
        result = {}
        for _ in range(7):
            result = algo.train()
            if first is None:
                first = result["episode_reward_mean"]
        assert result["episode_reward_mean"] > 1.5 * first
        algo.stop()

    def test_vtrace_on_policy_matches_returns(self):
        """On-policy with no clipping active, V-trace targets equal
        discounted returns (rho = c = 1)."""
        import jax.numpy as jnp
        import optax

        from ray_memory_management_tpu.rllib.impala import (
            make_impala_update,
        )
        from ray_memory_management_tpu.rllib.models import ac_init

        # run the jitted update twice with identical inputs; finite
        # losses and param change prove the scan path is wired
        import jax

        params = ac_init(jax.random.key(0), 4, 2, (8,))
        opt = optax.adam(1e-2)
        update = make_impala_update(opt, gamma=0.9, vf_coeff=0.5,
                                    entropy_coeff=0.0)
        state = opt.init(params)
        obs = jax.random.normal(jax.random.key(1), (5, 4))
        actions = jnp.zeros(5, dtype=jnp.int32)
        logp = jnp.log(jnp.full(5, 0.5))
        rewards = jnp.ones(5)
        dones = jnp.zeros(5)
        p2, state, stats = update(params, state, obs, actions, logp,
                                  rewards, dones, jnp.float32(0.0))
        assert np.isfinite(float(stats["total_loss"]))
        assert not np.allclose(
            np.asarray(p2["pi"][0]["w"]),
            np.asarray(params["pi"][0]["w"]))


class TestTuneIntegration:
    def test_algorithm_is_trainable(self, rmt_start_regular):
        """Algorithms drop into the Tuner (the reference runs all RLlib
        training through Tune)."""
        from ray_memory_management_tpu.rllib import PPO
        from ray_memory_management_tpu.tune import TuneConfig, Tuner

        results = Tuner(
            PPO,
            param_space={
                "env_spec": "CartPole",
                "env_config": {"max_episode_steps": 50},
                "num_rollout_workers": 0,
                "rollout_fragment_length": 100,
                "train_batch_size": 200,
                "lr": 1e-3,
                "seed": 0,
                "hidden": (16,),
            },
            tune_config=TuneConfig(metric="episode_reward_mean",
                                   mode="max", num_samples=1,
                                   max_iterations=2),
        ).fit()
        best = results.get_best_result("episode_reward_mean", "max")
        assert best.metrics["training_iteration"] == 2


class TestAPPO:
    def test_learns_async(self, rmt_start_regular):
        """Async PPO: IMPALA's overlap with the clipped surrogate —
        learning regression mirrors IMPALA's (appo.py)."""
        from ray_memory_management_tpu.rllib import APPOConfig

        algo = (APPOConfig()
                .environment("CartPole",
                             env_config={"max_episode_steps": 200})
                .rollouts(num_rollout_workers=2,
                          rollout_fragment_length=200)
                .training(train_batch_size=1600, lr=1e-3,
                          clip_param=0.3)
                .debugging(seed=1)
                .build())
        first = None
        result = {}
        for _ in range(7):
            result = algo.train()
            if first is None:
                first = result["episode_reward_mean"]
        assert result["episode_reward_mean"] > 1.5 * first
        # the surrogate really ran against the behavior policy
        assert "mean_is_ratio" in result
        assert 0.2 < result["mean_is_ratio"] < 5.0
        algo.stop()


class TestPG:
    def test_learns_cartpole(self):
        """Plain REINFORCE with a value baseline improves CartPole —
        single pass per batch, no ratio/clip (pg.py; the reference's
        pg_tf_policy.py:31 loss)."""
        from ray_memory_management_tpu.rllib import PGConfig

        algo = (PGConfig()
                .environment("CartPole",
                             env_config={"max_episode_steps": 200})
                .rollouts(num_rollout_workers=0,
                          rollout_fragment_length=400)
                .training(train_batch_size=1600, lr=1e-3,
                          entropy_coeff=0.02)
                .debugging(seed=1)
                .build())
        assert algo.num_sgd_iter == 1  # PG: no trust region, one pass
        first = None
        best = 0.0
        result = {}
        for _ in range(15):
            result = algo.train()
            if first is None:
                first = result["episode_reward_mean"]
            best = max(best, result["episode_reward_mean"])
            if best > 100:
                break
        assert best > max(1.5 * first, 50), (first, best, result)
        algo.stop()

    def test_a2c_preset(self):
        from ray_memory_management_tpu.rllib import A2CConfig

        algo = (A2CConfig()
                .environment("CartPole",
                             env_config={"max_episode_steps": 100})
                .rollouts(num_rollout_workers=0,
                          rollout_fragment_length=200)
                .training(train_batch_size=400)
                .debugging(seed=0)
                .build())
        r = algo.train()
        assert r["num_env_steps_sampled"] >= 400
        assert "vf_loss" in r
        algo.stop()


class TestMultiAgent:
    def test_env_contract(self):
        """Dict obs/rewards/dones keyed by agent id, __all__ signalling
        (multi_agent.py; the reference's MultiAgentEnv contract,
        rllib/env/multi_agent_env.py:23)."""
        from ray_memory_management_tpu.rllib import MultiCartPole
        from ray_memory_management_tpu.rllib.multi_agent import ALL_DONE

        env = MultiCartPole(n_agents=3, max_episode_steps=20)
        obs = env.reset(seed=0)
        assert set(obs) == {"agent_0", "agent_1", "agent_2"}
        obs, rew, term, trunc, _ = env.step(
            {aid: 0 for aid in env.agent_ids})
        assert set(rew) <= set(env.agent_ids)
        assert ALL_DONE in term and ALL_DONE in trunc
        # run to the time limit: __all__ truncation fires
        for _ in range(25):
            live = [a for a in obs]
            if not live:
                break
            obs, rew, term, trunc, _ = env.step({a: 0 for a in live})
            if term[ALL_DONE] or trunc[ALL_DONE]:
                break
        assert term[ALL_DONE] or trunc[ALL_DONE]

    def test_fragment_contract(self):
        """Shared-policy fragments stay flat-fragment valid: every
        segment ends done=1 and the bootstrap is exactly 0, so GAE and
        V-trace consumers need no changes."""
        from ray_memory_management_tpu.rllib.multi_agent import (
            MultiAgentRolloutWorker)

        w = MultiAgentRolloutWorker(
            "MultiCartPole", {"n_agents": 2, "max_episode_steps": 20},
            (16,), seed=0)
        batch = w.sample(120)
        n = len(batch[sb.ACTIONS])
        assert n >= 120  # agent transitions, may overshoot one env step
        assert batch[sb.BOOTSTRAP][0] == 0.0
        # the batch ends at a segment boundary by construction
        assert batch[sb.DONES][-1] == 1.0
        assert len(batch[sb.ADVANTAGES]) == n
        stats = w.episode_stats()
        assert stats["episodes"] > 0

    def test_shared_policy_ppo_learns(self):
        """PPO trains the shared policy over a MultiAgentEnv with no
        learner changes — reward (summed over agents) improves."""
        from ray_memory_management_tpu.rllib import PPOConfig

        algo = (PPOConfig()
                .environment("MultiCartPole",
                             env_config={"n_agents": 2,
                                         "max_episode_steps": 200})
                .rollouts(num_rollout_workers=0,
                          rollout_fragment_length=400)
                .training(train_batch_size=1600, lr=3e-3, num_sgd_iter=8,
                          sgd_minibatch_size=256)
                .debugging(seed=1)
                .build())
        first = None
        best = 0.0
        result = {}
        for _ in range(10):
            result = algo.train()
            if first is None:
                first = result["episode_reward_mean"]
            best = max(best, result["episode_reward_mean"])
            if best > 200:
                break
        # two agents, so a mediocre shared policy already sums ~40;
        # learning should clearly beat the start
        assert best > max(1.5 * first, 100), (first, best)
        algo.stop()

    def test_remote_multi_agent_workers(self, rmt_start_regular):
        from ray_memory_management_tpu.rllib import IMPALAConfig

        algo = (IMPALAConfig()
                .environment("MultiCartPole",
                             env_config={"n_agents": 2,
                                         "max_episode_steps": 50})
                .rollouts(num_rollout_workers=2,
                          rollout_fragment_length=100)
                .training(train_batch_size=400)
                .debugging(seed=0)
                .build())
        r = algo.train()
        assert r["num_env_steps_sampled"] >= 400
        algo.stop()


class TestConnectors:
    """Env->policy transform pipeline (the reference's connector
    framework, rllib/connectors/): unit contracts per transform, state
    round-trip, and an end-to-end PPO run through a pipeline."""

    def test_obs_normalizer_stats(self):
        from ray_memory_management_tpu.rllib import ObsNormalizer

        norm = ObsNormalizer()
        rng = np.random.default_rng(0)
        outs = [norm.observe(rng.normal(5.0, 2.0, 3).astype(np.float32))
                for _ in range(2000)]
        tail = np.stack(outs[500:])
        assert abs(float(tail.mean())) < 0.2
        assert 0.7 < float(tail.std()) < 1.3

        # state round-trips into a fresh instance
        norm2 = ObsNormalizer()
        norm2.set_state(norm.state())
        x = np.ones(3, np.float32)
        np.testing.assert_allclose(norm2.observe(x), norm.observe(x),
                                   rtol=1e-5)

    def test_frame_stack_and_clip(self):
        from ray_memory_management_tpu.rllib import ClipReward, FrameStack
        from ray_memory_management_tpu.rllib.connectors import (
            ConnectorPipeline,
        )

        fs = FrameStack(k=3)
        assert fs.obs_dim(2) == 6
        first = fs.on_reset(np.array([1.0, 2.0], np.float32))
        np.testing.assert_allclose(first, [1, 2, 1, 2, 1, 2])
        second = fs.observe(np.array([3.0, 4.0], np.float32))
        np.testing.assert_allclose(second, [1, 2, 1, 2, 3, 4])

        clip = ClipReward(limit=1.0)
        assert clip.reward(7.5) == 1.0 and clip.reward(-3.0) == -1.0

        pipe = ConnectorPipeline([("frame_stack", {"k": 2}),
                                  ("clip_reward", {"limit": 2.0})])
        assert pipe.obs_dim(4) == 8
        assert pipe.reward(9.0) == 2.0
        st = pipe.state()
        pipe2 = ConnectorPipeline([("frame_stack", {"k": 2}),
                                   ("clip_reward", {"limit": 2.0})])
        pipe2.set_state(st)

    def test_ppo_trains_through_pipeline(self):
        """PPO with obs-norm + frame-stack: the model is sized for the
        widened observation and learning still happens."""
        from ray_memory_management_tpu.rllib import PPOConfig

        algo = (PPOConfig()
                .environment("CartPole",
                             env_config={"max_episode_steps": 200})
                .rollouts(num_rollout_workers=0,
                          rollout_fragment_length=400)
                .training(train_batch_size=1600, lr=3e-3, num_sgd_iter=8,
                          sgd_minibatch_size=256)
                .connectors([("obs_norm", {}), ("frame_stack", {"k": 2})])
                .debugging(seed=1)
                .build())
        assert algo.obs_dim == 8  # 4-dim cartpole obs stacked twice
        first = None
        result = {}
        for _ in range(8):
            result = algo.train()
            if first is None:
                first = result["episode_reward_mean"]
        assert result["episode_reward_mean"] > max(1.5 * first, 40), result
        algo.stop()

    def test_unknown_connector_rejected(self):
        from ray_memory_management_tpu.rllib.connectors import (
            build_pipeline,
        )

        with pytest.raises(ValueError, match="unknown connector"):
            build_pipeline([("nope", {})])

    def test_connector_state_rides_checkpoints(self):
        """Running obs-norm statistics travel with the weights: a
        restored policy must see the SAME normalization it trained with
        (a cold normalizer would feed it wildly different inputs)."""
        from ray_memory_management_tpu.rllib import PPOConfig

        cfg = (PPOConfig()
               .environment("CartPole",
                            env_config={"max_episode_steps": 100})
               .rollouts(num_rollout_workers=0,
                         rollout_fragment_length=200)
               .training(train_batch_size=400)
               .connectors([("obs_norm", {})])
               .debugging(seed=2))
        algo = cfg.build()
        algo.train()
        count_before = algo._infer_pipeline.stages[0]._count
        assert count_before > 0
        obs = np.array([0.01, 0.2, 0.02, -0.1], np.float32)
        action_before = algo.compute_single_action(obs)
        blob = algo.save()
        algo.stop()

        algo2 = cfg.build()
        # nearly cold: only the worker's initial env reset passed through
        assert algo2._infer_pipeline.stages[0]._count <= 1
        algo2.restore(blob)
        assert algo2._infer_pipeline.stages[0]._count == count_before
        assert algo2.compute_single_action(obs) == action_before
        algo2.stop()

    def test_connectors_rejected_by_dqn_sac(self):
        from ray_memory_management_tpu.rllib import DQNConfig, SACConfig

        for cfg in (DQNConfig().environment("CartPole"),
                    SACConfig().environment("Pendulum")):
            cfg.connectors([("obs_norm", {})])
            with pytest.raises(ValueError, match="connectors"):
                cfg.build()


class TestRecurrentPPO:
    def test_scan_matches_stepwise(self):
        """The learner's scan unroll (with done resets) reproduces the
        rollout's step-by-step path exactly — the invariant that makes
        fragments valid training sequences (recurrent.py)."""
        import jax
        import jax.numpy as jnp

        from ray_memory_management_tpu.rllib.recurrent import (
            lstm_ac_init, lstm_ac_seq, lstm_ac_step, lstm_zero_state)

        params = lstm_ac_init(jax.random.key(0), 4, 2, 16, 16)
        T = 12
        obs = np.asarray(
            jax.random.normal(jax.random.key(1), (T, 4)), np.float32)
        dones = np.zeros(T, np.float32)
        dones[4] = 1.0  # episode boundary mid-fragment
        h, c = lstm_zero_state(16)
        step_logits = []
        for t in range(T):
            logits, _, h, c = lstm_ac_step(
                params, jnp.asarray(obs[t]), jnp.asarray(h),
                jnp.asarray(c))
            step_logits.append(np.asarray(logits))
            if dones[t]:
                h, c = lstm_zero_state(16)
        seq_logits, _ = lstm_ac_seq(
            params, jnp.asarray(obs), jnp.asarray(dones),
            *map(jnp.asarray, lstm_zero_state(16)))
        np.testing.assert_allclose(np.stack(step_logits),
                                   np.asarray(seq_logits), rtol=2e-5,
                                   atol=2e-5)

    def test_learns_memory_cue_task(self):
        """A POMDP where memory is the WHOLE task: a cue appears only at
        t=0 and the policy is rewarded each later step for acting on it.
        At decision time the observation is identical for both cues, so
        a feedforward policy caps at chance (~half the max return) while
        the LSTM carries the cue forward (the reference's use_lstm
        contract on partially observable tasks)."""
        from ray_memory_management_tpu.rllib import (RecurrentPPOConfig,
                                                     register_env)

        class MemoryCue:
            """obs [cue_active, cue_value]; reward 1 per step for
            matching the remembered cue after it disappears."""

            observation_dim = 2
            num_actions = 2

            def __init__(self, length: int = 8):
                self.length = length
                self._rng = np.random.default_rng(0)
                self._cue = 1
                self._t = 0

            def reset(self, seed=None):
                if seed is not None:
                    self._rng = np.random.default_rng(seed)
                self._cue = int(self._rng.integers(2))
                self._t = 0
                return np.array([1.0, 2.0 * self._cue - 1.0], np.float32)

            def step(self, action):
                self._t += 1
                reward = float(action == self._cue) if self._t > 1 else 0.0
                done = self._t >= self.length
                return (np.zeros(2, np.float32), reward, done, False, {})

        register_env("MemoryCue", lambda **kw: MemoryCue(**kw))
        algo = (RecurrentPPOConfig()
                .environment("MemoryCue", env_config={"length": 8})
                .rollouts(num_rollout_workers=0,
                          rollout_fragment_length=200)
                .training(train_batch_size=1200, lr=3e-3, num_sgd_iter=8,
                          sgd_minibatch_seqs=3, lstm_dim=16,
                          embed_dim=16)
                .debugging(seed=1)
                .build())
        best = 0.0
        result = {}
        for _ in range(15):
            result = algo.train()
            best = max(best, result["episode_reward_mean"])
            if best > 6.5:
                break
        # max return 7.0 (rewards at t=2..8); memoryless chance ~3.5
        assert best > 5.0, (best, result)
        # recurrent inference API: the cue must steer later actions
        a0, state = algo.compute_single_action(
            np.array([1.0, 1.0], np.float32))
        a_pos, _ = algo.compute_single_action(
            np.zeros(2, np.float32), state)
        _, state_neg = algo.compute_single_action(
            np.array([1.0, -1.0], np.float32))
        a_neg, _ = algo.compute_single_action(
            np.zeros(2, np.float32), state_neg)
        assert a_pos == 1 and a_neg == 0  # memory drives the action
        algo.stop()

    def test_remote_recurrent_workers(self, rmt_start_regular):
        from ray_memory_management_tpu.rllib import RecurrentPPOConfig

        algo = (RecurrentPPOConfig()
                .environment("CartPole",
                             env_config={"max_episode_steps": 50})
                .rollouts(num_rollout_workers=2,
                          rollout_fragment_length=100)
                .training(train_batch_size=400, lstm_dim=16,
                          embed_dim=16)
                .debugging(seed=0)
                .build())
        r = algo.train()
        assert r["num_env_steps_sampled"] >= 400
        assert r["num_sequences"] >= 4
        algo.stop()
