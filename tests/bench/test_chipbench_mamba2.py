"""The program's Mamba-2 against the plain reference
(``benchmarks/chip/reference/mamba2.py``) at tiny widths on the CPU, and
the LM cell's block: its matmul precision, and the model's named scopes in
its op names."""
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _chipbench_lm as lm
import _chipbench_tiny as tiny
from chipbench import model_scopes, registry


def _family_and_reference():
    return (registry.load_module([tiny.BENCH], "families", "mamba2"),
            registry.load_module([tiny.BENCH], "reference", "mamba2"))


@pytest.mark.parametrize("seq", [64, 56], ids=["whole_chunks", "ragged"])
def test_program_loss_and_gradients_match_the_reference(seq):
    """Seeded random weights in the benchmark's init ranges; 56 tokens are
    not a whole number of 16-token chunks, so the program pads its chunked
    SSD while the reference's quadratic form needs no chunks at all."""
    fam, ref = _family_and_reference()
    cfg = lm.tiny_config()
    params = fam.init_params(cfg, jax.random.PRNGKey(3))
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, seq), 0, cfg["vocab_size"], jnp.int32)
    batch = (tokens, jnp.zeros((2,), jnp.int32))
    got_loss, got = jax.value_and_grad(fam.program_loss(cfg, "float32"))(params, batch)
    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.value_and_grad(ref.loss)(params, batch, cfg)
    assert abs(float(got_loss) - float(want_loss)) <= 1e-6 * abs(float(want_loss))
    for path, g, w in zip(jax.tree_util.tree_leaves_with_path(want),
                          jax.tree.leaves(got), jax.tree.leaves(want)):
        scale = float(jnp.max(jnp.abs(w)))
        assert scale > 0, jax.tree_util.keystr(path[0])
        err = float(jnp.max(jnp.abs(g - w))) / scale
        assert err <= 1e-4, (jax.tree_util.keystr(path[0]), err)


def test_reference_decay_mask_is_the_ssd_recurrence():
    """The quadratic form equals the recurrence h_t = exp(dt_t A) h_{t-1} +
    dt_t B_t x_t, y_t = C_t h_t + D x_t, stepped token by token."""
    _, ref = _family_and_reference()
    k = iter(jax.random.split(jax.random.PRNGKey(0), 6))
    bsz, n_tok, h, p, g, n = 1, 12, 2, 3, 1, 4
    x = jax.random.normal(next(k), (bsz, n_tok, h, p))
    dt = jax.nn.softplus(jax.random.normal(next(k), (bsz, n_tok, h)))
    a = -jnp.exp(jax.random.normal(next(k), (h,)))
    b = jax.random.normal(next(k), (bsz, n_tok, g, n))
    c = jax.random.normal(next(k), (bsz, n_tok, g, n))
    d = jax.random.normal(next(k), (h,))
    state, ys = np.zeros((bsz, h, p, n)), []
    for t in range(n_tok):
        decay = np.exp(np.asarray(dt[:, t] * a))[..., None, None]
        state = state * decay + np.einsum("bh,bhp,bn->bhpn", dt[:, t], x[:, t], b[:, t, 0])
        ys.append(np.einsum("bhpn,bn->bhp", state, c[:, t, 0]) + np.asarray(d)[:, None] * x[:, t])
    with jax.default_matmul_precision("highest"):
        got = ref.ssd(x, dt, a, b, c, d)
    np.testing.assert_allclose(got, np.stack(ys, axis=1), rtol=1e-5, atol=1e-5)


@pytest.fixture(scope="module")
def block(tmp_path_factory):
    """The tiny LM cell's program after set-up, and its block's shapes."""
    import capture_pisco_trace

    root = lm.make_root(tmp_path_factory.mktemp("bench"))
    cell = registry.load_cell(root, lm.NAME)
    train = cell.runner.train
    prog = train.build(cell, 2**31 + 5)
    sess = train.setup(prog)
    specs = capture_pisco_trace.batch_specs(prog.sampler, prog.rounds_per_block)
    return prog, sess, specs


def test_every_dot_general_of_the_block_carries_highest(block):
    """The configuration states float32 at ``highest``: no contraction of
    the round, the model, the SSD or the loss is left at the default."""
    prog, sess, specs = block
    text = prog.block_fn.lower(jax.eval_shape(lambda: sess.state), *specs).as_text()
    dots = [line for line in text.splitlines() if "stablehlo.dot_general" in line]
    assert len(dots) > 50
    assert all("precision = [HIGHEST, HIGHEST]" in line for line in dots)


def test_block_scopes_hold_the_ssd_forward_and_backward(block):
    """The compiled block's op-name map: the SSD's contractions of the
    forward pass (``jvp``) and of the backward pass (``transpose``) lie
    under ``mamba2.ssd``, in the local phase and in the communication
    step alike; every model scope holds operations."""
    import capture_pisco_trace

    prog, sess, _ = block
    (names,) = capture_pisco_trace.block_op_names(prog, sess.state).values()
    scopes = {model_scopes.scope_in(n) for n in names.values()}
    assert set(model_scopes.SCOPES) <= scopes
    ssd = [n for n in names.values()
           if model_scopes.scope_in(n) == "mamba2.ssd" and n.endswith("dot_general")]
    for phase in ("pisco.local/", "pisco.comm/"):
        for pass_ in ("(jvp(", "(transpose(jvp("):
            assert any(phase in n and pass_ in n for n in ssd), (phase, pass_)


def test_fleet_block_is_unchanged_by_the_model_scopes(monkeypatch, tmp_path):
    """The model's scopes live in the LM only: the fleet cell's compiled
    block holds none of them, and is the same text without them."""
    root = tiny.make_root(tmp_path)
    cell = registry.load_cell(root, "tiny.fleet")
    train = cell.runner
    prog = train.build(cell, 11)
    sess = train.setup(prog)

    import capture_pisco_trace

    specs = capture_pisco_trace.batch_specs(prog.sampler, prog.rounds_per_block)
    state = jax.eval_shape(lambda: sess.state)
    scoped = prog.block_fn.lower(state, *specs).compile().as_text()
    real = jax.named_scope
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext()
                        if name in model_scopes.MODEL_SCOPES else real(name))
    plain = train.build(cell, 11).block_fn.lower(state, *specs).compile().as_text()
    op_names = lambda text: re.findall(r'op_name="([^"]*)"', text)
    assert not any(s in n for n in op_names(scoped) for s in model_scopes.MODEL_SCOPES)
    assert op_names(scoped) == op_names(plain)

    def strip(text):
        """Less the source locations, which name the scope's caller."""
        text = re.sub(r" (source_file|source_line|stack_frame_id)=[^ }]*", "", text)
        tables = re.compile(r"^(FileNames|FunctionNames|FileLocations|StackFrames|\d+ .*)$")
        return "\n".join(line for line in text.splitlines() if not tables.match(line))

    assert strip(scoped) == strip(plain)
