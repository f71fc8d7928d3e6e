"""The reference's own weights are the program's, bit for bit, and its
forward agrees with the program's greedy tokens at a tiny size.  (The
test imports the program; the reference does not.)"""

import jax
import jax.numpy as jnp
import numpy as np

import tiny
from harness import local, reference


def test_weights_from_the_seed_match_the_program():
    from repro.models.registry import get_model

    cfg = tiny.config()
    arch = local.arch_of(cfg)
    seed = local.model_seed(2 ** 35 + 9)
    ours = reference.make_weights(cfg, seed)
    theirs = get_model(arch).init(jax.random.PRNGKey(seed), arch)
    flat = {"/".join(str(k.key) for k in path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(theirs)[0]}
    assert sorted(flat) == sorted(ours)
    for name, leaf in flat.items():
        assert leaf.dtype == ours[name].dtype, name
        np.testing.assert_array_equal(np.asarray(leaf, np.float32),
                                      np.asarray(ours[name], np.float32),
                                      err_msg=name)


def test_control_reads_wider_than_the_reference():
    """The float8 control's first choices lie further below the float32
    reference's best than the float32 reference's own do (which lie at
    0), and its logits differ."""
    cfg = tiny.config()
    w = reference.make_weights(cfg, 3)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg["vocab_size"], size=(2, 64))
    x = reference.hidden(cfg, w, tokens)
    xc = reference.hidden(cfg, w, tokens, prec="fp8")
    for i in range(2):
        ref = reference.logits(cfg, w, x[i])
        low = reference.logits(cfg, w, xc[i], prec="fp8")
        own = reference.gap(ref, np.asarray(jnp.argmax(ref, -1)))
        assert float(own.max()) == 0.0
        assert float(reference.gap(ref, np.asarray(
            jnp.argmax(low, -1))).max()) > 0.0
        assert float(jnp.abs(ref - low).max()) > 1e-3
