"""Search, gradient-free and model-based RLlib-slim tests: the env
contract, GAE math, SlateQ, MBPETS, AlphaZero and ES/ARS."""

import numpy as np
import pytest

from ray_memory_management_tpu.rllib import CartPole, make_env, register_env
from ray_memory_management_tpu.rllib import sample_batch as sb


class TestEnv:
    def test_cartpole_contract(self):
        env = CartPole(max_episode_steps=50)
        obs = env.reset(seed=3)
        assert obs.shape == (4,) and obs.dtype == np.float32
        total = 0
        for _ in range(60):
            obs, r, term, trunc, _ = env.step(1)
            total += r
            if term or trunc:
                break
        assert term or trunc
        assert total <= 50

    def test_register_env(self):
        register_env("TinyPole", lambda: CartPole(max_episode_steps=10))
        env = make_env("TinyPole")
        assert env.max_episode_steps == 10

    def test_unknown_env(self):
        with pytest.raises(ValueError):
            make_env("NoSuchEnv")


class TestGAE:
    def test_hand_computed(self):
        rewards = np.array([1.0, 1.0, 1.0], dtype=np.float32)
        values = np.array([0.5, 0.5, 0.5], dtype=np.float32)
        dones = np.array([0.0, 0.0, 1.0], dtype=np.float32)
        adv, targets = sb.compute_gae(
            rewards, values, dones, last_value=9.9, gamma=0.9, lam=1.0)
        # terminal step ignores the bootstrap
        assert adv[2] == pytest.approx(1.0 - 0.5)
        # lam=1: discounted monte-carlo returns minus values
        ret1 = 1.0 + 0.9 * 1.0 + 0.81 * 1.0
        assert targets[0] == pytest.approx(ret1)

    def test_bootstrap_mid_episode(self):
        rewards = np.array([0.0], dtype=np.float32)
        values = np.array([0.0], dtype=np.float32)
        dones = np.array([0.0], dtype=np.float32)
        adv, targets = sb.compute_gae(
            rewards, values, dones, last_value=2.0, gamma=0.5, lam=0.9)
        assert targets[0] == pytest.approx(1.0)  # 0 + 0.5 * 2.0


class TestSlateQ:
    def test_exact_slate_beats_myopic_and_random(self):
        """SlateQ's choice-model decomposition + exact pruned slate
        optimization must clearly beat both random slates (~8.9/ep) and
        the myopic appeal-greedy (~6.4/ep) on the clickbait-structured
        interest-evolution env (slateq.py; the reference's
        rllib/algorithms/slateq contract — measured 13.0 at iter 14,
        oracle ~16; thresholds leave slack)."""
        from ray_memory_management_tpu.rllib import SlateQConfig

        algo = (SlateQConfig()
                .training(lr=1e-3, gamma=0.95, updates_per_iter=40)
                .debugging(seed=7)
                .build())
        for _ in range(15):
            r = algo.train()
        assert r["episode_reward_mean"] > 10.5, r["episode_reward_mean"]

        # the greedy slate is a valid slate over the real corpus
        slate = algo.compute_slate()
        assert len(slate) == algo.slate_size
        assert len(set(slate)) == algo.slate_size
        assert all(0 <= d < algo.n_docs for d in slate)

        # save/restore round-trips the item-value network
        blob = algo.save()
        import jax

        before = jax.tree_util.tree_leaves(
            jax.tree_util.tree_map(np.asarray, algo.params))
        algo.stop()
        from ray_memory_management_tpu.rllib import SlateQConfig as C2

        algo2 = C2().debugging(seed=7).build()
        algo2.restore(blob)
        after = jax.tree_util.tree_leaves(
            jax.tree_util.tree_map(np.asarray, algo2.params))
        for a, b in zip(before, after):
            np.testing.assert_allclose(a, b)
        algo2.stop()

    def test_decomposed_slate_value_prefers_value_over_appeal(self):
        """The exact slate optimizer must REFUSE a clickbait item that
        steals probability mass: given one high-appeal/zero-value doc
        and several modest-appeal/high-value docs, the chosen slate
        excludes the clickbait row (top-k by s*Q greedy would seat it
        — the regret mode the exact optimizer exists to avoid)."""
        import jax.numpy as jnp

        from ray_memory_management_tpu.rllib.slateq import (
            _best_slate_value, _slate_combos)

        scores = jnp.asarray([50.0, 2.0, 2.0, 2.0, 0.1])
        q = jnp.asarray([0.05, 1.0, 1.0, 1.0, 1.0])
        combos = _slate_combos(5, 2)
        v, top_idx, best = _best_slate_value(scores, q, combos, 5)
        chosen = {int(top_idx[r]) for r in combos[int(best)]}
        assert 0 not in chosen, chosen  # clickbait excluded
        # sanity: its value beats the clickbait-seated slate {0,1}
        s0 = (50.0 * 0.05 + 2.0 * 1.0) / (52.0 + 1.0)
        assert float(v) > s0


def _leaves(params):
    import jax

    return jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(np.asarray, params))


class TestMBPETS:
    @staticmethod
    def _build():
        from ray_memory_management_tpu.rllib import MBPETSConfig

        return (MBPETSConfig()
                .environment("Pendulum",
                             env_config={"max_episode_steps": 100})
                .training(lr=1e-3, horizon=25, population=256,
                          cem_iters=5, model_updates_per_iter=100,
                          random_steps=1200)
                .debugging(seed=3)
                .build())

    # 476 s in the tier-1 run: 22 iterations of CEM planning per env step
    @pytest.mark.slow
    def test_model_based_planning_improves_pendulum(self):
        """The model-based family (mbrl.py; reference Dreamer/MBMPO
        class): a learned dynamics ensemble + jit'd CEM planning must
        clearly beat the random-policy baseline (~-650/ep at these
        settings; measured -349 rolling / -294 greedy after 30 iters,
        thresholds leave slack). Also pins the disagreement penalty's
        reason for existing: without it CEM exploits out-of-distribution
        model optimism and DEGRADES below random."""
        algo = self._build()
        first = None
        for _ in range(22):
            r = algo.train()
            if first is None and not np.isnan(r["episode_reward_mean"]):
                first = r["episode_reward_mean"]
        assert r["model_loss"] < 0.05  # the dynamics model converged
        assert r["episode_reward_mean"] > first + 100, (
            first, r["episode_reward_mean"])
        assert r["episode_reward_mean"] > -560  # beats random (~-650)
        algo.stop()

    def test_save_restore_roundtrips_the_ensemble(self):
        """save/restore round-trips the stacked ensemble. Two iterations
        of the learning run's config stay inside its 1,200 random steps:
        the ensemble trains and the planner is never traced."""
        algo = self._build()
        for _ in range(2):
            r = algo.train()
        assert r["num_updates"] > 0  # trained away from the seed's init
        blob = algo.save()
        before = _leaves(algo.params)
        algo.stop()
        from ray_memory_management_tpu.rllib import MBPETSConfig as C2

        algo2 = (C2()
                 .environment("Pendulum",
                              env_config={"max_episode_steps": 100})
                 .debugging(seed=3)
                 .build())
        algo2.restore(blob)
        after = _leaves(algo2.params)
        for a, b in zip(before, after):
            np.testing.assert_allclose(a, b)
        algo2.stop()


class TestAlphaZero:
    def test_mcts_finds_forced_win_without_learning(self):
        """PUCT search alone (uniform priors, zero values) must
        concentrate visits on the winning move of a tactics position —
        the search half of AlphaZero, isolated from the network
        (alphazero.py BatchedMCTS; reference mcts.py)."""
        from ray_memory_management_tpu.rllib.alphazero import (
            BatchedMCTS, TicTacToe)

        def uniform_eval(obs):
            B = obs.shape[0]
            return (np.full((B, 9), 1.0 / 9, np.float64), np.zeros(B))

        g = TicTacToe()
        for mv in (0, 3, 1, 4):  # X holds 0,1: the winning move is 2
            g.step(mv)
        mcts = BatchedMCTS(uniform_eval, n_sims=200,
                           rng=np.random.default_rng(0))
        pi = mcts.search_batch([g], add_noise=False)[0]
        assert int(pi.argmax()) == 2
        assert pi[2] > 0.6  # visits concentrate, not a lucky argmax

    # 106 s in the tier-1 run: ten iterations of 32 searched games
    @pytest.mark.slow
    def test_self_play_learns_tictactoe(self):
        """MCTS-guided self-play + the AlphaZero loss beats a random
        opponent decisively after a short run (the reference's
        alpha_zero learning contract, CI-scaled: measured 58W/0L/2D in
        60 games at these settings; thresholds leave slack)."""
        from ray_memory_management_tpu.rllib import (
            AlphaZeroConfig, TicTacToe)

        algo = (AlphaZeroConfig()
                .training(lr=3e-3, num_simulations=32, games_per_iter=32,
                          num_sgd_iter=10)
                .debugging(seed=1)
                .build())
        first_loss = None
        for _ in range(10):
            r = algo.train()
            if first_loss is None:
                first_loss = r["policy_loss"]
        assert r["policy_loss"] < first_loss  # the policy head converges

        rng = np.random.default_rng(42)
        wins = losses = 0
        for _ in range(60):
            g = TicTacToe()
            while g.outcome() is None:
                if g.player == 1:
                    a = algo.compute_single_action(g, greedy_sims=24)
                else:
                    a = int(rng.choice(np.flatnonzero(g.legal())))
                g.step(a)
            out = g.outcome()
            wins += out == 1
            losses += out == -1
        assert wins >= 45 and losses <= 6, (wins, losses)

        algo.stop()

    def test_save_restore_roundtrips_the_two_heads(self):
        """save/restore round-trips the policy and value heads after one
        short iteration of self-play."""
        from ray_memory_management_tpu.rllib import AlphaZeroConfig

        algo = (AlphaZeroConfig()
                .training(lr=3e-3, num_simulations=8, games_per_iter=4,
                          num_sgd_iter=2)
                .debugging(seed=1)
                .build())
        algo.train()
        blob = algo.save()
        before = _leaves(algo.params)
        algo.stop()
        algo2 = AlphaZeroConfig().debugging(seed=1).build()
        algo2.restore(blob)
        after = _leaves(algo2.params)
        for a, b in zip(before, after):
            np.testing.assert_allclose(a, b)
        algo2.stop()


class TestES:
    # 99 s in the tier-1 run: up to 25 iterations of 64 episodes each
    @pytest.mark.slow
    def test_learns_cartpole_gradient_free(self):
        """Evolution strategies improves CartPole with no gradients
        through the policy — antithetic seed-derived perturbations,
        centered-rank weighting (es.py; the reference's
        tuned_examples/es contract, CI-scaled)."""
        from ray_memory_management_tpu.rllib import ESConfig

        algo = (ESConfig()
                .environment("CartPole",
                             env_config={"max_episode_steps": 200})
                .rollouts(num_rollout_workers=0)
                .training(lr=0.03, sigma=0.1, episodes_per_batch=64)
                .debugging(seed=3)
                .build())
        best = 0.0
        result = {}
        for _ in range(25):
            result = algo.train()
            best = max(best, result["fitness_mean"])
            if best > 120:
                break
        assert best > 60, (best, result)
        a = algo.compute_single_action(
            np.array([0.01, 0.0, 0.02, 0.0], np.float32))
        assert a in (0, 1)
        algo.stop()

    def test_save_restore_roundtrips_theta(self):
        """save/restore round-trips the flat parameter vector after one
        iteration."""
        from ray_memory_management_tpu.rllib import ESConfig

        def build():
            return (ESConfig()
                    .environment("CartPole",
                                 env_config={"max_episode_steps": 200})
                    .rollouts(num_rollout_workers=0)
                    .training(lr=0.03, sigma=0.1, episodes_per_batch=8)
                    .debugging(seed=3)
                    .build())

        algo = build()
        algo.train()
        blob = algo.save()
        theta = algo.theta.copy()
        algo.stop()
        algo2 = build()
        algo2.restore(blob)
        np.testing.assert_allclose(algo2.theta, theta)
        algo2.stop()

    def test_ars_learns_cartpole_with_linear_policy(self):
        """ARS improves CartPole with a LINEAR policy — top-k direction
        selection, return-std step scaling, and the running observation
        filter (ars.py; the reference's rllib/algorithms/ars contract,
        CI-scaled)."""
        from ray_memory_management_tpu.rllib import ARSConfig

        algo = (ARSConfig()
                .environment("CartPole",
                             env_config={"max_episode_steps": 200})
                .rollouts(num_rollout_workers=0)
                .training(lr=0.3, sigma=0.5, num_directions=32,
                          top_directions=16)
                .debugging(seed=5)
                .build())
        best = 0.0
        result = {}
        for _ in range(25):
            result = algo.train()
            best = max(best, result["fitness_mean"])
            if best > 120:
                break
        assert best > 60, (best, result)
        assert result["filter_count"] > 0  # the obs filter accumulated
        a = algo.compute_single_action(
            np.array([0.01, 0.0, 0.02, 0.0], np.float32))
        assert a in (0, 1)
        # save/restore round-trips theta AND the observation filter
        blob = algo.save()
        theta = algo.theta.copy()
        count = algo.filter.count
        algo.stop()
        from ray_memory_management_tpu.rllib import ARSConfig as C2

        algo2 = (C2()
                 .environment("CartPole",
                              env_config={"max_episode_steps": 200})
                 .rollouts(num_rollout_workers=0)
                 .debugging(seed=5)
                 .build())
        algo2.restore(blob)
        np.testing.assert_allclose(algo2.theta, theta)
        assert algo2.filter.count == count
        algo2.stop()

    def test_ars_filter_delta_merge(self):
        """Worker filter increments fold into the master filter exactly
        (the MeanStdFilter delta-sync invariant)."""
        from ray_memory_management_tpu.rllib.ars import _ObsFilter

        master = _ObsFilter(3)
        obs = np.arange(12, dtype=np.float64).reshape(4, 3)
        master.merge({"count": 4.0, "sum": obs.sum(0),
                      "sumsq": (obs * obs).sum(0)})
        snap = master.snapshot()
        np.testing.assert_allclose(snap["mean"], obs.mean(0), rtol=1e-6)
        np.testing.assert_allclose(snap["std"], obs.std(0), rtol=1e-5)

    def test_seed_reconstruction_matches_worker(self):
        """The learner's jit-reconstructed perturbation equals the
        worker's — the invariant replacing the shared noise table."""
        import jax
        import jax.numpy as jnp

        from ray_memory_management_tpu.rllib.es import (_perturbation,
                                                        make_es_update)

        dim = 37
        eps_np = _perturbation(1234, dim)
        eps_jit = np.asarray(jax.random.normal(
            jax.random.PRNGKey(1234), (dim,), dtype=jnp.float32))
        np.testing.assert_allclose(eps_np, eps_jit)
        # a single-seed update moves theta exactly along eps
        update = make_es_update(lr=1.0, sigma=1.0, l2=0.0)
        theta = np.zeros(dim, np.float32)
        out = np.asarray(update(jnp.asarray(theta),
                                jnp.asarray([1234]),
                                jnp.asarray([1.0], jnp.float32)))
        np.testing.assert_allclose(out, eps_np, rtol=1e-6)

    def test_remote_workers_shard_seeds(self, rmt_start_regular):
        from ray_memory_management_tpu.rllib import ESConfig

        algo = (ESConfig()
                .environment("CartPole",
                             env_config={"max_episode_steps": 50})
                .rollouts(num_rollout_workers=2)
                .training(episodes_per_batch=8)
                .debugging(seed=0)
                .build())
        r = algo.train()
        assert r["episodes_this_iter"] == 8
        assert r["timesteps_total"] > 0  # env steps counted (Tune keys on it)
        algo.stop()
