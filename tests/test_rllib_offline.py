"""Offline RLlib-slim tests: dataset writer/reader, BC, off-policy
estimation, MARWIL, CQL and the contextual bandits."""

import numpy as np

from ray_memory_management_tpu.rllib import sample_batch as sb


class TestOfflineRL:
    """Offline stack: dataset IO, behavior cloning, and importance-
    sampling off-policy evaluation (rllib/offline/ json_writer.py:31,
    json_reader.py:198, estimators/importance_sampling.py)."""

    def test_bc_clones_expert_from_dataset(self, tmp_path):
        """Record a scripted 'expert' (CartPole pole-direction policy),
        clone it with BC, and verify both imitation accuracy and that the
        cloned policy performs like the expert — all without any env
        interaction during training."""
        import numpy as np

        from ray_memory_management_tpu.rllib import BCConfig, collect_dataset
        from ray_memory_management_tpu.rllib.offline import DatasetReader

        def expert(obs):
            a = 1 if obs[2] + 0.3 * obs[3] > 0 else 0  # push toward lean
            return a, -0.05  # near-deterministic behavior logp

        path = collect_dataset(
            "CartPole", str(tmp_path / "data"), num_steps=4000,
            policy=expert, env_config={"max_episode_steps": 200}, seed=0,
            shard_size=1500)
        reader = DatasetReader(path)
        assert reader.num_samples == 4000
        import os

        assert len(os.listdir(tmp_path / "data")) >= 3  # really sharded

        algo = (BCConfig()
                .environment("CartPole",
                             env_config={"max_episode_steps": 200})
                .offline_data(input_path=path)
                .training(lr=1e-3, train_batch_size=256,
                          updates_per_step=100, eval_episodes=2)
                .debugging(seed=0)
                .build())
        result = {}
        for _ in range(8):
            result = algo.train()
            if result["action_match"] > 0.95:
                break
        assert result["action_match"] > 0.9, result
        # the scripted expert balances for ~200 steps; the clone should
        # get most of the way there (random policy scores ~20)
        assert result["episode_reward_mean"] > 100, result

        # save/restore round-trips (the Tune Trainable contract — BC has
        # no rollout workers, so restore must not try to sync weights)
        blob = algo.save()
        obs = np.array([0.01, 0.0, 0.05, 0.1], np.float32)
        action = algo.compute_single_action(obs)
        algo.stop()
        algo2 = (BCConfig()
                 .environment("CartPole",
                              env_config={"max_episode_steps": 200})
                 .offline_data(input_path=path)
                 .debugging(seed=0)
                 .build())
        algo2.restore(blob)
        assert algo2.compute_single_action(obs) == action
        algo2.stop()

    def test_dataset_writer_shards_and_reader_episodes(self, tmp_path):
        import numpy as np

        from ray_memory_management_tpu.rllib import (
            DatasetReader,
            DatasetWriter,
        )
        from ray_memory_management_tpu.rllib import sample_batch as sb

        w = DatasetWriter(str(tmp_path / "d"), shard_size=100)
        for i in range(3):
            n = 120
            w.write({
                sb.OBS: np.full((n, 2), i, np.float32),
                sb.ACTIONS: np.zeros(n, np.int32),
                sb.REWARDS: np.ones(n, np.float32),
                sb.DONES: np.asarray(([0.0] * 59 + [1.0]) * 2, np.float32),
            })
        w.close()
        r = DatasetReader(str(tmp_path / "d"))
        assert r.num_samples == 360
        eps = list(r.iter_episodes())
        assert len(eps) == 6 and all(
            sb.batch_size(e) == 60 for e in eps)
        mb = r.sample(32)
        assert sb.batch_size(mb) == 32

        # a truncated trailing fragment is NOT an episode by default
        w2 = DatasetWriter(str(tmp_path / "d2"))
        w2.write({sb.OBS: np.zeros((10, 2), np.float32),
                  sb.ACTIONS: np.zeros(10, np.int32),
                  sb.REWARDS: np.ones(10, np.float32),
                  sb.DONES: np.asarray([0, 0, 0, 1] + [0] * 6,
                                       np.float32)})
        w2.close()
        r2 = DatasetReader(str(tmp_path / "d2"))
        assert len(list(r2.iter_episodes())) == 1
        assert len(list(r2.iter_episodes(include_partial=True))) == 2

    def test_importance_sampling_ope(self, tmp_path):
        """Sanity contract of the IS/WIS estimators: evaluating the
        behavior policy itself must reproduce the behavior return, and a
        policy weighted toward better episodes must score higher."""
        import numpy as np

        from ray_memory_management_tpu.rllib import (
            collect_dataset,
            importance_sampling_estimate,
        )
        from ray_memory_management_tpu.rllib.offline import DatasetReader

        path = collect_dataset(
            "CartPole", str(tmp_path / "d"), num_steps=2000,
            env_config={"max_episode_steps": 100}, seed=1)
        reader = DatasetReader(path)

        # target == behavior (uniform random): ratios are exactly 1
        n_act = 2
        uniform = lambda obs, acts: np.full(len(acts), -np.log(n_act))
        est = importance_sampling_estimate(reader, uniform, gamma=1.0)
        assert abs(est["wis_estimate"] - est["behavior_mean_return"]) < 1e-6
        assert est["episodes"] > 5
        assert est["effective_sample_size"] > est["episodes"] * 0.99


def _mixed_quality_dataset(tmp_path, expert_steps: int = 1250,
                           random_steps: int = 3750):
    """Mostly-random CartPole transitions with an expert minority — the
    regime where advantage weighting (MARWIL) and conservatism (CQL)
    matter and plain BC is dragged toward the (bad) majority policy."""
    from ray_memory_management_tpu.rllib import collect_dataset

    def expert(obs):
        a = 1 if obs[2] + 0.3 * obs[3] > 0 else 0
        return a, -0.05

    path = str(tmp_path / "mixed")
    collect_dataset("CartPole", path, num_steps=expert_steps,
                    policy=expert,
                    env_config={"max_episode_steps": 200}, seed=0)
    collect_dataset("CartPole", path, num_steps=random_steps, policy=None,
                    env_config={"max_episode_steps": 200}, seed=1)
    from ray_memory_management_tpu.rllib.offline import DatasetReader

    # both recordings must land (a second same-directory writer used to
    # overwrite the first's shards)
    assert DatasetReader(path).num_samples == expert_steps + random_steps
    return path


class TestMARWIL:
    def test_beats_bc_on_mixed_data(self, tmp_path):
        """Advantage re-weighting follows the expert half of a mixed
        dataset where plain cloning imitates the average policy
        (marwil.py's Wang et al. 2018 contract; the reference's
        tuned_examples/marwil/cartpole-marwil.yaml, CI-scaled).
        beta=0 must degenerate to BC exactly (uniform weights)."""
        from ray_memory_management_tpu.rllib import (BCConfig,
                                                     MARWILConfig)

        path = _mixed_quality_dataset(tmp_path)

        def run(config):
            algo = (config
                    .environment("CartPole",
                                 env_config={"max_episode_steps": 500})
                    .offline_data(input_path=path)
                    .training(lr=1e-3, train_batch_size=256,
                              updates_per_step=100, eval_episodes=3)
                    .debugging(seed=0)
                    .build())
            result = {}
            for _ in range(8):
                result = algo.train()
            algo.stop()
            return result

        marwil = run(MARWILConfig())
        bc = run(BCConfig())
        # the re-weighted clone should clearly outperform the average-
        # policy clone on mixed data
        assert marwil["episode_reward_mean"] > 120, marwil
        assert (marwil["episode_reward_mean"]
                > bc["episode_reward_mean"] + 30), (marwil, bc)
        # weights really spread (expert rows upweighted vs random rows)
        assert marwil["mean_weight"] > 0

    def test_beta_zero_weights_are_uniform(self, tmp_path):
        from ray_memory_management_tpu.rllib import MARWILConfig

        path = _mixed_quality_dataset(tmp_path, expert_steps=300,
                                      random_steps=300)
        algo = (MARWILConfig()
                .environment("CartPole",
                             env_config={"max_episode_steps": 50})
                .offline_data(input_path=path)
                .training(beta=0.0, train_batch_size=128,
                          updates_per_step=8, eval_episodes=1)
                .debugging(seed=0)
                .build())
        result = algo.train()
        # exp(0 * adv / c) == 1 for every row
        assert abs(result["mean_weight"] - 1.0) < 1e-5, result
        algo.stop()


class TestCQL:
    def test_learns_cartpole_offline(self, tmp_path):
        """Conservative Q-learning reaches the reward threshold from a
        fixed mixed-quality dataset with no environment interaction
        (cql.py; the reference's CQL contract on offline data). The
        conservative penalty must be active (positive logsumexp gap)."""
        from ray_memory_management_tpu.rllib import CQLConfig

        path = _mixed_quality_dataset(tmp_path)
        algo = (CQLConfig()
                .environment("CartPole",
                             env_config={"max_episode_steps": 500})
                .offline_data(input_path=path)
                .training(lr=5e-4, gamma=0.99, cql_alpha=1.0,
                          train_batch_size=256, updates_per_step=150,
                          target_update_freq=100, eval_episodes=3)
                .debugging(seed=0)
                .build())
        result = {}
        best = 0.0
        for _ in range(10):
            result = algo.train()
            best = max(best, result["episode_reward_mean"])
            if best > 150:
                break
        assert best > 120, (best, result)
        assert result["cql_penalty"] > 0, result
        # checkpoint round-trip preserves the target net + Adam moments
        blob = algo.save()
        algo.stop()
        algo2 = (CQLConfig()
                 .environment("CartPole",
                              env_config={"max_episode_steps": 500})
                 .offline_data(input_path=path)
                 .training(train_batch_size=256, updates_per_step=1)
                 .debugging(seed=0)
                 .build())
        algo2.restore(blob)
        assert algo2._updates_done == algo._updates_done
        algo2.stop()

    def test_boundary_semantics_across_recordings(self, tmp_path):
        """Appended recordings are independent streams: returns must not
        accumulate across the boundary, a recording's truncated tail is
        invalid, TD successors never cross recordings, and the reader
        keeps the column intersection of mixed-schema shards."""
        import numpy as np

        from ray_memory_management_tpu.rllib import sample_batch as sb
        from ray_memory_management_tpu.rllib.collector import NEXT_OBS
        from ray_memory_management_tpu.rllib.cql import derive_next_obs
        from ray_memory_management_tpu.rllib.marwil import episode_returns
        from ray_memory_management_tpu.rllib.offline import (
            DatasetReader, DatasetWriter)

        # recording A rows 0-2 (episode 0-1, truncated tail 2); B rows 3-5
        rewards = np.ones(6, np.float32)
        dones = np.array([0, 1, 0, 0, 1, 0], np.float32)
        starts = np.array([0, 3])
        returns, valid = episode_returns(rewards, dones, 1.0, starts)
        assert valid.tolist() == [1, 1, 0, 1, 1, 0]
        assert returns[0] == 2  # stops at A's own episode end
        assert returns[2] == 1  # tail: no bleed into B's returns
        assert returns[3] == 2

        obs = np.arange(6, dtype=np.float32)[:, None]
        data = {sb.OBS: obs, sb.DONES: dones,
                sb.ACTIONS: np.zeros(6, np.int32), sb.REWARDS: rewards}
        out = derive_next_obs(data, starts)
        # both truncated tails (rows 2 and 5) dropped, episodes intact
        assert len(out[sb.OBS]) == 4
        np.testing.assert_allclose(out[NEXT_OBS][0], obs[1])

        # reader: two writers, one legacy (no next_obs) — intersection
        w1 = DatasetWriter(str(tmp_path / "d"))
        w1.write({sb.OBS: obs[:3], sb.ACTIONS: np.zeros(3, np.int32),
                  sb.REWARDS: rewards[:3], sb.DONES: dones[:3],
                  NEXT_OBS: obs[:3]})
        w1.close()
        w2 = DatasetWriter(str(tmp_path / "d"))
        w2.write({sb.OBS: obs[3:], sb.ACTIONS: np.zeros(3, np.int32),
                  sb.REWARDS: rewards[3:], sb.DONES: dones[3:]})
        w2.close()
        r = DatasetReader(str(tmp_path / "d"))
        assert r.num_samples == 6
        assert len(r.recording_starts) == 2
        assert NEXT_OBS not in r.data  # intersection, never a ragged col
        # iter_episodes: exactly the two complete episodes, no merged
        # cross-recording fragment
        eps = list(r.iter_episodes())
        assert len(eps) == 2
        assert all(sb.batch_size(e) == 2 for e in eps)

    def test_derive_next_obs_for_legacy_datasets(self, tmp_path):
        """Datasets recorded before the next_obs column can still feed
        CQL: successors are back-filled from the time order and the
        truncated tail row is dropped."""
        import numpy as np

        from ray_memory_management_tpu.rllib import sample_batch as sb
        from ray_memory_management_tpu.rllib.cql import derive_next_obs

        obs = np.arange(10, dtype=np.float32)[:, None]
        dones = np.zeros(10, np.float32)
        dones[4] = 1.0  # one completed episode, then a truncated tail
        data = {sb.OBS: obs, sb.DONES: dones,
                sb.ACTIONS: np.zeros(10, np.int32),
                sb.REWARDS: np.ones(10, np.float32)}
        out = derive_next_obs(data)
        assert len(out[sb.OBS]) == 9  # non-terminal tail row dropped
        from ray_memory_management_tpu.rllib.collector import NEXT_OBS

        # within-episode successor: next_obs[t] == obs[t+1]
        np.testing.assert_allclose(out[NEXT_OBS][0], obs[1])
        np.testing.assert_allclose(out[NEXT_OBS][7], obs[8])


class TestBandits:
    def test_linucb_sublinear_regret(self):
        """LinUCB's per-step regret collapses as the per-arm posteriors
        sharpen (bandit.py; the reference's BanditLinUCB contract —
        tuned_examples/bandit). Also: the whole state round-trips."""
        from ray_memory_management_tpu.rllib import BanditLinUCBConfig

        algo = (BanditLinUCBConfig()
                .environment("LinearBandit",
                             env_config={"num_arms": 5, "context_dim": 8,
                                         "noise": 0.05, "seed": 7})
                .training(alpha=1.0, steps_per_iter=200)
                .debugging(seed=0)
                .build())
        first = algo.train()["regret_mean"]
        last = {}
        for _ in range(4):
            last = algo.train()
        assert last["regret_mean"] < 0.5 * first, (first, last)
        assert last["regret_mean"] < 0.1, last
        blob = algo.save()
        algo.stop()

        algo2 = (BanditLinUCBConfig()
                 .environment("LinearBandit",
                              env_config={"num_arms": 5, "context_dim": 8,
                                          "noise": 0.05, "seed": 7})
                 .debugging(seed=0)
                 .build())
        algo2.restore(blob)
        import numpy as np

        assert np.allclose(algo2.get_weights()["A"],
                           algo.get_weights()["A"])
        algo2.stop()

    def test_lints_learns(self):
        """Thompson sampling reaches the same sublinear-regret regime
        through posterior draws instead of a UCB bonus."""
        from ray_memory_management_tpu.rllib import BanditLinTSConfig

        algo = (BanditLinTSConfig()
                .environment("LinearBandit",
                             env_config={"num_arms": 4, "context_dim": 6,
                                         "noise": 0.05, "seed": 3})
                .training(alpha=0.5, steps_per_iter=200)
                .debugging(seed=1)
                .build())
        first = algo.train()["regret_mean"]
        last = {}
        for _ in range(4):
            last = algo.train()
        assert last["regret_mean"] < 0.5 * first, (first, last)
        algo.stop()
