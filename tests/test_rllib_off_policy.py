"""Off-policy RLlib-slim tests: replay buffers, DQN, SAC, TD3/DDPG,
QMix, MADDPG and R2D2 (learning regressions scaled to CI budgets and
checkpoint round-trips)."""

import numpy as np
import pytest

from ray_memory_management_tpu.rllib import (PrioritizedReplayBuffer,
                                              ReplayBuffer)


class TestReplay:
    def test_ring_overwrite(self):
        buf = ReplayBuffer(capacity=8, seed=0)
        buf.add_batch({"x": np.arange(6)})
        assert len(buf) == 6
        buf.add_batch({"x": np.arange(6, 12)})
        assert len(buf) == 8
        sample = buf.sample(32)
        assert set(np.unique(sample["x"])) <= set(range(4, 12))

    def test_prioritized(self):
        buf = PrioritizedReplayBuffer(capacity=16, alpha=1.0, seed=0)
        buf.add_batch({"x": np.arange(8)})
        buf.update_priorities(np.array([3]), np.array([100.0]))
        sample = buf.sample(256, beta=1.0)
        # element 3 dominates the distribution
        frac = float(np.mean(sample["x"] == 3))
        assert frac > 0.5
        assert sample["_weights"].max() == pytest.approx(1.0)


class TestQMix:
    def test_mixer_is_monotonic_in_agent_qs(self):
        """dQ_tot/dQ_i >= 0 for every agent at random states/qs — the
        property (abs on hypernetwork weights) that makes decentralized
        greedy execution consistent with the centralized critic
        (qmix_policy.py's QMixer)."""
        import jax
        import jax.numpy as jnp

        from ray_memory_management_tpu.rllib.qmix import mix, qmix_init

        params = qmix_init(jax.random.key(0), obs_dim=5, num_actions=2,
                           n_agents=3, state_dim=4, mixing_dim=8)
        B = 16
        state = jax.random.normal(jax.random.key(1), (B, 4))
        qs = jax.random.normal(jax.random.key(2), (B, 3))
        grads = jax.vmap(jax.grad(
            lambda q, s: mix(params, s[None], q[None], 3, 8)[0]
        ))(qs, state)
        assert float(jnp.min(grads)) >= 0.0

    def test_learns_two_step_coordination(self):
        """The QMIX paper's two-step game: greedy independent learners
        plateau at the safe 7-reward branch; monotonic value
        factorization must find the coordinated 8 (threshold > 7.0)."""
        from ray_memory_management_tpu.rllib import QMixConfig

        algo = (QMixConfig()
                .environment("TwoStepCoop")
                .rollouts(num_rollout_workers=0,
                          rollout_fragment_length=64)
                .training(lr=3e-3, train_batch_size=64,
                          learning_starts=128, updates_per_step=16,
                          target_network_update_freq=50,
                          epsilon_timesteps=1500, gamma=0.99)
                .debugging(seed=3)
                .build())
        result = {}
        for _ in range(40):
            result = algo.train()
            if (result["episode_reward_mean"] or 0) > 7.5:
                break
        assert result["episode_reward_mean"] > 7.0, result
        # greedy decentralized execution coordinates on the 8 branch
        from ray_memory_management_tpu.rllib.qmix import TwoStepCoop

        env = TwoStepCoop()
        obs = env.reset()
        acts = algo.compute_actions(obs)
        obs, _, _, _, _ = env.step(acts)
        assert acts["agent_0"] == 1  # picked the risky branch
        acts = algo.compute_actions(obs)
        r = env.step(acts)[1]["agent_0"]
        assert r == 8.0
        algo.stop()

    def test_checkpoint_roundtrip(self):
        from ray_memory_management_tpu.rllib import QMixConfig

        cfg = (QMixConfig()
               .environment("TwoStepCoop")
               .rollouts(num_rollout_workers=0,
                         rollout_fragment_length=32)
               .training(train_batch_size=32, learning_starts=32)
               .debugging(seed=4))
        algo = cfg.build()
        algo.train()
        blob = algo.save()
        env2 = cfg.build()
        env2.restore(blob)
        import jax.tree_util as jtu
        import numpy as np_

        for a, b in zip(jtu.tree_leaves(algo.params),
                        jtu.tree_leaves(env2.params)):
            np_.testing.assert_array_equal(np_.asarray(a),
                                           np_.asarray(b))
        algo.stop()
        env2.stop()


class TestDQN:
    def test_learns_cartpole(self):
        """Off-policy learning regression: double-DQN with replay + target
        net reaches the reward threshold (the reference's
        tuned_examples/dqn/cartpole-dqn.yaml contract, CI-scaled)."""
        from ray_memory_management_tpu.rllib import DQNConfig

        algo = (DQNConfig()
                .environment("CartPole",
                             env_config={"max_episode_steps": 200})
                .rollouts(num_rollout_workers=0,
                          rollout_fragment_length=200)
                .training(lr=1e-3, train_batch_size=128,
                          learning_starts=400,
                          target_network_update_freq=100,
                          updates_per_step=64,
                          epsilon_timesteps=4000,
                          replay_buffer_capacity=20_000)
                .debugging(seed=1)
                .build())
        first = None
        result = {}
        for _ in range(40):
            result = algo.train()
            if first is None:
                first = result["episode_reward_mean"]
            if (result["episode_reward_mean"] or 0) > 100:
                break
        assert result["episode_reward_mean"] > max(1.5 * (first or 9), 60), \
            result["episode_reward_mean"]
        assert result["replay_size"] > 500
        assert result["num_updates"] > 0
        algo.stop()

    def test_remote_workers_and_checkpoint(self, rmt_start_regular,
                                           tmp_path):
        from ray_memory_management_tpu.rllib import DQNConfig

        cfg = (DQNConfig()
               .environment("CartPole",
                            env_config={"max_episode_steps": 100})
               .rollouts(num_rollout_workers=2,
                         rollout_fragment_length=50)
               .training(learning_starts=100, updates_per_step=4)
               .debugging(seed=0))
        algo = cfg.build()
        r = algo.train()
        assert r["num_env_steps_sampled"] >= 100
        # the schedule pins epsilon exactly: eps_initial + frac * span
        expected_eps = 1.0 + min(
            1.0, (r["timesteps_total"] - r["num_env_steps_sampled"])
            / 10_000) * (0.02 - 1.0)
        assert r["epsilon"] == pytest.approx(expected_eps)
        ckpt = str(tmp_path / "dqn")
        import os as _os
        _os.makedirs(ckpt, exist_ok=True)
        algo.save_checkpoint(ckpt)
        algo.stop()

        algo2 = cfg.build()
        algo2.load_checkpoint(ckpt)
        a = algo2.compute_single_action(np.zeros(4, np.float32))
        assert a in (0, 1)
        assert algo2._updates_done == r["num_updates"]
        algo2.stop()


class TestSAC:
    # 111 s in the tier-1 run: up to 80 iterations of 200 updates each
    @pytest.mark.slow
    def test_learns_pendulum(self):
        """Continuous-control learning regression: twin-Q SAC with
        entropy auto-tuning improves pendulum swing-up well past the
        random-policy plateau (~-1200..-1400 per 200-step episode; the
        reference's tuned_examples/sac/pendulum-sac.yaml contract,
        CI-scaled)."""
        from ray_memory_management_tpu.rllib import SACConfig

        algo = (SACConfig()
                .environment("Pendulum",
                             env_config={"max_episode_steps": 200})
                .rollouts(num_rollout_workers=0,
                          rollout_fragment_length=200)
                .training(lr=1e-3, train_batch_size=128,
                          learning_starts=500, random_steps=500,
                          updates_per_step=200, tau=0.005)
                .debugging(seed=1)
                .build())
        result = {}
        for _ in range(80):
            result = algo.train()
            rm = result.get("episode_reward_mean")
            if rm is not None and rm > -700:
                break
        assert result["episode_reward_mean"] > -900, result
        assert result["num_updates"] > 1000
        # entropy auto-tuning drove alpha off its 1.0 init
        assert 0 < result["alpha"] < 0.9
        # the deterministic (mean) policy emits in-range actions
        import numpy as np

        a = algo.compute_single_action(
            np.array([1.0, 0.0, 0.0], np.float32))
        assert a.shape == (1,) and abs(float(a[0])) <= 2.0
        algo.stop()

    def test_checkpoint_roundtrip(self, tmp_path):
        """save/restore preserves learner state (target nets, temperature,
        optimizer progress) — the Trainable save/restore contract."""
        from ray_memory_management_tpu.rllib import SACConfig

        def build():
            return (SACConfig()
                    .environment("Pendulum",
                                 env_config={"max_episode_steps": 50})
                    .rollouts(num_rollout_workers=0,
                              rollout_fragment_length=64)
                    .training(train_batch_size=32, learning_starts=64,
                              random_steps=64, updates_per_step=4)
                    .debugging(seed=3)
                    .build())

        import jax
        import numpy as np

        algo = build()
        for _ in range(3):
            algo.train()
        blob = algo.save()
        updates = algo._updates_done
        alpha = float(algo.log_alpha)
        moments = [np.asarray(leaf).sum()
                   for leaf in jax.tree_util.tree_leaves(algo.opt_states)]
        algo.stop()

        algo2 = build()
        algo2.restore(blob)
        assert algo2._updates_done == updates
        assert abs(float(algo2.log_alpha) - alpha) < 1e-6
        # Adam moments really restored (not re-init'd to zeros)
        moments2 = [np.asarray(leaf).sum()
                    for leaf in jax.tree_util.tree_leaves(algo2.opt_states)]
        assert len(moments2) == len(moments)
        np.testing.assert_allclose(moments2, moments, rtol=1e-6)
        algo2.train()  # must keep training from the restored state
        assert algo2._updates_done > updates
        algo2.stop()


class TestTD3:
    # 98 s in the tier-1 run: up to 80 iterations of 200 updates each
    @pytest.mark.slow
    def test_learns_pendulum(self):
        """Deterministic-policy learning regression: twin-Q TD3 with
        delayed policy updates and target smoothing clears the
        random-policy plateau on pendulum swing-up (the reference's
        tuned_examples/td3/pendulum-td3.yaml contract, CI-scaled)."""
        from ray_memory_management_tpu.rllib import TD3Config

        algo = (TD3Config()
                .environment("Pendulum",
                             env_config={"max_episode_steps": 200})
                .rollouts(num_rollout_workers=0,
                          rollout_fragment_length=200)
                .training(lr=1e-3, train_batch_size=128,
                          learning_starts=500, random_steps=500,
                          updates_per_step=200, tau=0.005,
                          explore_sigma=0.1)
                .debugging(seed=1)
                .build())
        result = {}
        for _ in range(80):
            result = algo.train()
            rm = result.get("episode_reward_mean")
            if rm is not None and rm > -700:
                break
        assert result["episode_reward_mean"] > -900, result
        assert result["num_updates"] > 1000
        # the actor updated on the delayed schedule, not every step
        a = algo.compute_single_action(
            np.array([1.0, 0.0, 0.0], np.float32))
        assert a.shape == (1,) and abs(float(a[0])) <= 2.0
        algo.stop()

    def test_ddpg_preset_and_checkpoint(self):
        """DDPGConfig is TD3 with the deltas off (single critic, delay 1,
        no smoothing); save/restore preserves target nets and Adam
        moments so training resumes exactly."""
        import jax

        from ray_memory_management_tpu.rllib import DDPGConfig

        def build():
            return (DDPGConfig()
                    .environment("Pendulum",
                                 env_config={"max_episode_steps": 50})
                    .rollouts(num_rollout_workers=0,
                              rollout_fragment_length=64)
                    .training(train_batch_size=32, learning_starts=64,
                              random_steps=64, updates_per_step=4)
                    .debugging(seed=3)
                    .build())

        algo = build()
        assert "q2" not in algo.params  # single critic
        assert algo.policy_delay == 1
        for _ in range(3):
            algo.train()
        blob = algo.save()
        updates = algo._updates_done
        moments = [np.asarray(leaf).sum()
                   for leaf in jax.tree_util.tree_leaves(algo.opt_states)]
        algo.stop()

        algo2 = build()
        algo2.restore(blob)
        assert algo2._updates_done == updates
        moments2 = [np.asarray(leaf).sum()
                    for leaf in jax.tree_util.tree_leaves(algo2.opt_states)]
        np.testing.assert_allclose(moments2, moments, rtol=1e-6)
        algo2.train()
        assert algo2._updates_done > updates
        algo2.stop()


class TestMADDPG:
    def test_learns_cooperative_rendezvous(self):
        """Centralized critics + decentralized actors improve the
        cooperative rendezvous reward (maddpg.py; the reference's
        rllib/algorithms/maddpg two-agent MPE contract, CI-scaled).
        Agents start ~1 apart (reward ~ -50/episode under random play)
        and must learn to close the distance."""
        from ray_memory_management_tpu.rllib import MADDPGConfig

        algo = (MADDPGConfig()
                .environment("Rendezvous",
                             env_config={"n_agents": 2,
                                         "max_episode_steps": 25})
                .training(lr=1e-3, gamma=0.95, train_batch_size=128,
                          random_steps=300, updates_per_iter=25)
                .debugging(seed=7)
                .build())
        first, best = None, -np.inf
        for _ in range(40):
            result = algo.train()
            r = result["episode_reward_mean"]
            if not np.isnan(r):
                if first is None:
                    first = r
                best = max(best, r)
            if first is not None and best > first + 3.0:
                break
        assert first is not None and best > first + 3.0, (first, best)

        # decentralized execution: actions come from the actors alone
        env = algo.env
        obs = env.reset(seed=123)
        acts = algo.compute_actions(obs)
        assert set(acts) == set(env.agent_ids)
        for a in acts.values():
            assert a.shape == (2,) and np.all(np.abs(a) <= 1.0)

        # save/restore round-trips the stacked params
        blob = algo.save()
        import jax

        before = jax.tree_util.tree_map(np.asarray, algo.params)
        algo.stop()
        from ray_memory_management_tpu.rllib import MADDPGConfig as C2

        algo2 = (C2()
                 .environment("Rendezvous",
                              env_config={"n_agents": 2,
                                          "max_episode_steps": 25})
                 .debugging(seed=7)
                 .build())
        algo2.restore(blob)
        after = jax.tree_util.tree_map(np.asarray, algo2.params)
        for a, b in zip(jax.tree_util.tree_leaves(before),
                        jax.tree_util.tree_leaves(after)):
            np.testing.assert_allclose(a, b)
        algo2.stop()

    def test_actor_grad_isolated_to_own_agent(self):
        """The MADDPG gradient: agent i's actor loss must produce ZERO
        gradient on agent j's actor (others' actions come from the
        batch, not their policies)."""
        import jax
        import jax.numpy as jnp

        from ray_memory_management_tpu.rllib.maddpg import maddpg_init

        n, do, da, B = 3, 6, 2, 4
        params = maddpg_init(jax.random.key(0), n, do, da, hidden=(8,))
        rng = np.random.default_rng(0)
        batch = (jnp.asarray(rng.standard_normal((B, n, do)),
                             jnp.float32),
                 jnp.asarray(rng.standard_normal((B, n, da)),
                             jnp.float32),
                 jnp.asarray(rng.standard_normal((B, n)), jnp.float32),
                 jnp.asarray(rng.standard_normal((B, n, do)),
                             jnp.float32),
                 jnp.zeros((B,), jnp.float32))

        # recreate the actor loss with a PER-AGENT mean to probe agent 0
        from ray_memory_management_tpu.rllib.maddpg import mlp_apply

        def actor_loss_agent0(pi_stacked):
            obs, act = batch[0], batch[1]
            obs_nb = jnp.swapaxes(obs, 0, 1)
            my = jax.vmap(lambda p, o: jnp.tanh(mlp_apply(p, o)))(
                pi_stacked, obs_nb)
            joint = act.at[:, 0].set(jnp.swapaxes(my, 0, 1)[:, 0])
            x = jnp.concatenate([obs.reshape(B, -1),
                                 joint.reshape(B, -1)], -1)
            q0 = mlp_apply(jax.tree_util.tree_map(lambda l: l[0],
                                                  params["q"]), x)
            return -jnp.mean(q0)

        grads = jax.grad(actor_loss_agent0)(params["pi"])
        leaves = jax.tree_util.tree_leaves(grads)
        for leaf in leaves:
            assert float(jnp.abs(leaf[0]).sum()) > 0  # own grad flows
            assert float(jnp.abs(leaf[1:]).sum()) == 0  # others' are zero


class TestR2D2:
    def test_learns_memory_cue_offpolicy(self):
        """Recurrent Q-learning from sequence replay with burn-in solves
        the same memory-cue POMDP RecurrentPPO does — off-policy, from
        stale stored sequences (r2d2.py; Kapturowski et al. 2019)."""
        from ray_memory_management_tpu.rllib import R2D2Config, register_env

        class MemoryCue:
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

        register_env("MemoryCueR2D2", lambda **kw: MemoryCue(**kw))
        algo = (R2D2Config()
                .environment("MemoryCueR2D2", env_config={"length": 8})
                .rollouts(num_rollout_workers=0)
                .training(lr=2e-3, seq_len=16, burn_in=2,
                          seqs_per_step=12, train_batch_seqs=16,
                          updates_per_step=16, target_update_freq=50,
                          lstm_dim=16, embed_dim=16,
                          epsilon_timesteps=4000)
                .debugging(seed=2)
                .build())
        best = 0.0
        result = {}
        for _ in range(30):
            result = algo.train()
            rm = result.get("episode_reward_mean")
            if rm is not None:
                best = max(best, rm)
            if best > 6.5:
                break
        # max return 7.0; memoryless chance ~3.5
        assert best > 5.0, (best, result)
        # the remembered cue must steer the greedy action
        _, state_pos = algo.compute_single_action(
            np.array([1.0, 1.0], np.float32))
        a_pos, _ = algo.compute_single_action(
            np.zeros(2, np.float32), state_pos)
        _, state_neg = algo.compute_single_action(
            np.array([1.0, -1.0], np.float32))
        a_neg, _ = algo.compute_single_action(
            np.zeros(2, np.float32), state_neg)
        assert a_pos == 1 and a_neg == 0
        algo.stop()

    def test_burn_in_warms_without_gradient(self):
        """No gradient may flow through the burn-in unroll: the shipped
        update's step must EQUAL one computed by warming the state
        outside autodiff entirely and differentiating only the tail
        (r2d2_tf_policy.py:113). If stop_gradient were dropped, the two
        would diverge."""
        import jax
        import jax.numpy as jnp
        import optax

        from ray_memory_management_tpu.rllib.r2d2 import (
            lstm_q_init, lstm_q_seq, make_r2d2_update)

        burn_in = 3
        params = lstm_q_init(jax.random.key(0), 2, 2, 8, 8)
        opt = optax.sgd(1e-2)  # SGD: the step IS the gradient, scaled
        update = make_r2d2_update(opt, gamma=0.9, burn_in=burn_in)
        N, T = 2, 10
        key = jax.random.key(1)
        obs = jax.random.normal(key, (N, T, 2))
        batch = (
            obs,
            jnp.zeros((N, T), jnp.int32),
            jnp.ones((N, T)),
            jnp.zeros((N, T)),
            jnp.zeros((N, 8)), jnp.zeros((N, 8)),
            jax.random.normal(jax.random.key(2), (N, 2)))
        state = opt.init(params)
        p_shipped, _, stats = update(params, params, state, batch)
        assert np.isfinite(float(stats["td_loss"]))

        # reference step: warm states OUTSIDE autodiff (no gradient can
        # possibly flow), then run the same update with burn_in=0 on the
        # tail only
        zeros8 = jnp.zeros((N, 8))
        warm = jax.vmap(
            lambda o, d, h, c: lstm_q_seq(params, o, d, h, c)[1]
        )(obs[:, :burn_in], jnp.zeros((N, burn_in)), zeros8, zeros8)
        bh, bc = warm
        update0 = make_r2d2_update(opt, gamma=0.9, burn_in=0)
        tail_batch = (
            obs[:, burn_in:],
            batch[1][:, burn_in:], batch[2][:, burn_in:],
            batch[3][:, burn_in:],
            jax.lax.stop_gradient(bh), jax.lax.stop_gradient(bc),
            batch[6])
        p_ref, _, _ = update0(params, params, opt.init(params),
                              tail_batch)
        for a, b in zip(jax.tree_util.tree_leaves(p_shipped),
                        jax.tree_util.tree_leaves(p_ref)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)

    def test_remote_sequence_collection(self, rmt_start_regular):
        from ray_memory_management_tpu.rllib import R2D2Config

        algo = (R2D2Config()
                .environment("CartPole",
                             env_config={"max_episode_steps": 50})
                .rollouts(num_rollout_workers=2)
                .training(seq_len=10, burn_in=2, seqs_per_step=4,
                          learning_starts_seqs=4, train_batch_seqs=4,
                          updates_per_step=2, lstm_dim=8, embed_dim=8)
                .debugging(seed=0)
                .build())
        r = algo.train()
        assert r["num_env_steps_sampled"] >= 40
        assert r["replay_seqs"] >= 4
        algo.stop()
