"""Optimizer, schedules, data pipeline, checkpointing."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.data.pipeline import MMapSource, PipelineConfig, SyntheticSource, write_corpus
from repro.optim import optimizers, schedules
from repro.train import checkpoint as ckpt_lib


# --------------------------------------------------------------------- optim
def test_adam_minimizes_quadratic():
    opt = optimizers.adam(0.1)
    params = {"w": jnp.asarray([5.0, -3.0])}
    state = opt.init(params)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}
        upd, state = opt.update(grads, state, params)
        params = optimizers.apply_updates(params, upd)
    assert float(jnp.abs(params["w"]).max()) < 1e-2


def test_adamw_decays_weights():
    opt = optimizers.adamw(0.01, weight_decay=0.5)
    params = {"w": jnp.asarray([10.0])}
    state = opt.init(params)
    grads = {"w": jnp.asarray([0.0])}
    upd, state = opt.update(grads, state, params)
    p2 = optimizers.apply_updates(params, upd)
    assert float(p2["w"][0]) < 10.0


def test_grad_clipping():
    big = {"w": jnp.full((4,), 1e6)}
    clipped, norm = optimizers.clip_by_global_norm(big, 1.0)
    assert float(optimizers.global_norm(clipped)) <= 1.0 + 1e-5
    assert float(norm) > 1e5


def test_schedules_shapes():
    for sched in [
        schedules.constant(1e-3),
        schedules.cosine(1e-3, 100, warmup=10),
        schedules.wsd(1e-3, 100, warmup=10),
    ]:
        vals = [float(sched(jnp.asarray(s))) for s in [0, 5, 50, 99]]
        assert all(v >= 0 for v in vals)
    wsd = schedules.wsd(1e-3, 100, warmup=10, decay_frac=0.2)
    assert abs(float(wsd(jnp.asarray(50))) - 1e-3) < 1e-9  # stable plateau
    assert float(wsd(jnp.asarray(99))) < 5e-4            # decayed
    assert float(wsd(jnp.asarray(5))) < 1e-3             # warming up


# ---------------------------------------------------------------------- data
def test_synthetic_deterministic_and_rank_disjoint():
    c0 = PipelineConfig(batch_size=4, seq_len=32, vocab=100, seed=7, rank=0, world=2)
    c1 = PipelineConfig(batch_size=4, seq_len=32, vocab=100, seed=7, rank=1, world=2)
    s0, s0b, s1 = SyntheticSource(c0), SyntheticSource(c0), SyntheticSource(c1)
    a = s0.batch_at(3)
    b = s0b.batch_at(3)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])  # deterministic
    c = s1.batch_at(3)
    assert not np.array_equal(a["tokens"], c["tokens"])      # ranks differ
    # labels are next-token
    np.testing.assert_array_equal(a["labels"][:, :-1], a["tokens"][:, 1:])


def test_mmap_source(tmp_path):
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 50, size=10000).astype(np.int32)
    path = str(tmp_path / "corpus.bin")
    write_corpus(path, toks)
    cfg = PipelineConfig(batch_size=3, seq_len=64, vocab=50, seed=1)
    src = MMapSource(path, cfg)
    b = src.batch_at(0)
    assert b["tokens"].shape == (3, 64)
    np.testing.assert_array_equal(
        src.batch_at(5)["tokens"], src.batch_at(5)["tokens"]
    )


# ----------------------------------------------------------------- checkpoint
def test_checkpoint_roundtrip_and_gc(tmp_path):
    ck = ckpt_lib.Checkpointer(str(tmp_path), keep=2, async_save=False)
    tree = {"a": jnp.arange(10.0), "b": {"c": jnp.ones((3, 3))}}
    for step in [10, 20, 30]:
        ck.save(step, jax.tree.map(lambda x: x * step, tree))
    assert ck.all_steps() == [20, 30]  # gc kept last 2
    restored, manifest = ck.restore(30, tree)
    np.testing.assert_allclose(np.asarray(restored["a"]), np.arange(10.0) * 30)
    assert manifest["step"] == 30


def test_checkpoint_async_and_auto_resume(tmp_path):
    ck = ckpt_lib.Checkpointer(str(tmp_path), async_save=True)
    tree = {"w": jnp.full((4,), 7.0)}
    ck.save(5, tree)
    ck.wait()
    restored, step = ckpt_lib.auto_resume(ck, tree)
    assert step == 5
    np.testing.assert_allclose(np.asarray(restored["w"]), 7.0)


def test_auto_resume_empty_dir(tmp_path):
    ck = ckpt_lib.Checkpointer(str(tmp_path))
    tree, step = ckpt_lib.auto_resume(ck, {"w": jnp.zeros(2)})
    assert tree is None and step == 0


# ---------------------------------------------------------- grad compression
def test_int8_error_feedback_converges():
    from repro.dist.grad_compress import ErrorFeedbackInt8

    comp = ErrorFeedbackInt8()
    params = {"w": jnp.asarray([2.0, -1.0])}
    state = comp.init(params)
    opt = optimizers.adam(0.05)
    ost = opt.init(params)
    for _ in range(300):
        grads = {"w": 2 * params["w"]}
        grads, state = comp.transform(grads, state)
        upd, ost = opt.update(grads, ost, params)
        params = optimizers.apply_updates(params, upd)
    assert float(jnp.abs(params["w"]).max()) < 5e-2


def test_topk_error_feedback_preserves_mass():
    from repro.dist.grad_compress import TopK

    comp = TopK(fraction=0.25)
    params = {"w": jnp.arange(16.0)}
    state = comp.init(params)
    grads = {"w": jnp.arange(16.0)}
    g1, state = comp.transform(grads, state)
    # error feedback: residual + next grad reappears
    g2, state = comp.transform(grads, state)
    total = np.asarray(g1["w"] + g2["w"])
    assert total.sum() > np.asarray(grads["w"]).sum()  # catching up on skipped mass


# ----------------------------------------------------------- synthetic data
def test_synthetic_tensor_same_in_every_process():
    """Seeded tensors must not depend on the process's string-hash salt."""
    import os
    import subprocess
    import sys

    code = (
        "import hashlib; from repro.data import synthetic_tensors as st; "
        "print(hashlib.sha256(st.load('pems_sf', seed=5).tobytes()).hexdigest())"
    )
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    digests = set()
    for salt in ("1", "2"):
        env = dict(os.environ, PYTHONHASHSEED=salt, PYTHONPATH=src)
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=120, check=True)
        digests.add(out.stdout.strip())
    assert len(digests) == 1
