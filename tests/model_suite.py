"""One conformance suite over ``tests/model_table.py``: every model
``models/stack.py`` walks, at ``tiny()`` on seeded weights in float32 on
the CPU, against its plain reference under ``benchmark/references/``. A
file ``tests/test_<model>.py`` takes it with

    ROWS = ("<model>",)
    globals().update(model_suite.tests_of(ROWS))

and holds beside it what only that model has. Each test is written once
and runs for the rows that have what it tests (a row without a routed
layer, or without shares, is left out of that test's parameters, never
skipped).

One ``Case`` a (row, share) makes the parameters, the program's outputs
and the reference's once, and jits each function once: the tests of a
case read them from it, and the module that took the suite keeps its
cases until it is done."""

from dataclasses import replace
from functools import cached_property
from importlib import import_module

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models import llama  # noqa: E402
from ray_tpu.ops.layers import Ctx  # noqa: E402
from ray_tpu.parallel import MeshSpec, build_mesh  # noqa: E402
from tests.model_table import ROWS as TABLE  # noqa: E402


class Case:
    """A row at one held share: what its tests compare, each made once."""

    def __init__(self, name, share):
        self.row = row = TABLE[name]
        self.share = share
        self.mod = import_module("ray_tpu.models." + name)
        self.ref = import_module("benchmark.references." + name + "_ref")
        self.cfg = getattr(self.mod, row.config).tiny(
            **row.tiny, **row.shares[share])
        params = self.mod.init_params(self.cfg, jax.random.PRNGKey(0))
        for n, kind in enumerate(params["layers"]):
            for i, (leaf, far) in enumerate(row.moved):
                if leaf in params["layers"][kind]:
                    w = params["layers"][kind][leaf]
                    params["layers"][kind][leaf] = w + far * jax.random.normal(
                        jax.random.PRNGKey(10 * n + i), w.shape)
        self.params = row.also_moved(params) if row.also_moved else params
        seed, shape, dtype = row.tokens
        self.tokens = np.random.default_rng(seed).integers(
            0, self.cfg.vocab_size, shape, dtype)

    def __iter__(self):
        """``mod, ref, cfg, params, tokens = case``."""
        return iter((self.mod, self.ref, self.cfg, self.params, self.tokens))

    @property
    def inputs(self):
        return self.tokens[:, :-self.row.ahead]

    @cached_property
    def program(self):
        """(logits, what the layers report) of the program's forward."""
        forward = self.row.forward or (lambda mod, cfg, p, t: mod.forward(
            cfg, p, t, keep_router_logits=True))
        with jax.default_matmul_precision("highest"):
            return jax.jit(lambda p, t: forward(
                self.mod, self.cfg, p, t, **self.batch))(
                    self.params, self.inputs)

    @cached_property
    def forced(self):
        return self.row.forced(self)

    def _forced(self, where):
        return self.forced if where in self.row.forced_in else {}

    @cached_property
    def want_logits(self):
        forced = self._forced("logits")
        return np.asarray(jax.jit(lambda p: self.ref.logits(
            self.cfg, p, self.inputs, **forced))(self.params))

    @cached_property
    def want(self):
        """The reference's ``token_nll``: per-position loss, terms, and
        what its layers report."""
        return self.ref.token_nll(self.cfg, self.params, self.tokens,
                                  **self._forced("nll"))

    @cached_property
    def batch(self):
        """What a batch brings beside its tokens (positions, a mask)."""
        return self.row.batch(self) if self.row.batch else {}

    @cached_property
    def _loss_and_gradient(self):
        """((loss, terms), every leaf's gradient of the loss): one compiled
        function for the terms' test and the gradients'."""
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(
                lambda p, t: self.mod.loss_terms(
                    self.cfg, p, {"tokens": t, **self.batch}),
                has_aux=True))(self.params, self.tokens)

    @cached_property
    def loss_terms(self):
        row = self.row
        if self.share in (row.gradient_shares or row.shares):
            return self._loss_and_gradient[0]
        with jax.default_matmul_precision("highest"):
            return jax.jit(lambda p, t: self.mod.loss_terms(
                self.cfg, p, {"tokens": t, **self.batch}))(
                    self.params, self.tokens)

    @cached_property
    def token_nll(self):
        """(per-position loss, reports) through the blocked head."""
        with jax.default_matmul_precision("highest"):
            return jax.jit(lambda p, t: self.mod.STACK.token_nll(
                self.cfg, p, t, head_block=16))(
                    self.params, jnp.asarray(self.tokens))

    @cached_property
    def gradients(self):
        """(the program's, the reference's) gradient of the whole loss,
        every leaf's."""
        if self.row.gradients:
            return self.row.gradients(self)
        return self._loss_and_gradient[1], jax.jit(jax.grad(
            lambda p: self.ref.loss(self.cfg, p, self.tokens)))(self.params)

    @cached_property
    def _weighted(self):
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(lambda p, w: (
                w * self.mod.STACK.token_nll(
                    self.cfg, p, jnp.asarray(self.tokens),
                    head_block=32)[0]).sum()))

    def weighted_gradient(self, weights):
        """(``sum(weights * per-position loss)`` through the blocked head,
        every leaf's gradient of it): one compiled function whatever the
        weights, for the weighted gradient's test and the weighted
        mean's."""
        with jax.default_matmul_precision("highest"):
            return self._weighted(self.params, jnp.asarray(weights))


def cases(rows, having=lambda row: True):
    """``case``'s parameters: every share of every row that has it."""
    return [pytest.param((name, share), id=f"{name}-{share}".rstrip("-"))
            for name in rows if having(TABLE[name])
            for share in TABLE[name].shares]


# test -> (the argument it is given for each row, how) ; ``case`` is the
# fixture below, anything else a plain parameter
_PARAMETERS = {
    "test_output_matches_the_reference": lambda rows: ("case", cases(rows)),
    "test_each_term_of_the_loss_matches_the_reference":
        lambda rows: ("case", cases(rows)),
    "test_every_leafs_gradient_matches_the_reference": lambda rows: (
        "case, group", [
            pytest.param((name, share), group, id=f"{name}-{share}-{group}"
                         .replace("--", "-"))
            for name in rows for share in (TABLE[name].gradient_shares
                                           or TABLE[name].shares)
            for group in TABLE[name].groups]),
    "test_reference_gradient_of_a_weighted_loss": lambda rows: (
        "case", cases(rows, lambda row: row.weighted is not None)),
    "test_training_loss_is_the_weighted_mean_of_token_nll": lambda rows: (
        "case", cases(rows, lambda row: row.blocked)),
    "test_first_step_against_the_reference_adamw": lambda rows: (
        "case", cases(rows, lambda row: row.blocked)),
    "test_variants_agree": lambda rows: ("case, what", [
        pytest.param((name, ""), what, id=f"{name}-{what}")
        for name in rows if TABLE[name].blocked
        for what in TABLE[name].blocked["variants"]]),
    "test_fsdp_train_step_matches_unsharded": lambda rows: (
        "case", cases(rows, lambda row: row.blocked)),
    "test_expert_shares_add_up_to_the_uncut_layer": lambda rows: (
        "row", [name for name in rows if TABLE[name].expert_shares]),
    "test_head_shares_add_up_to_the_whole_layers_attention": lambda rows: (
        "case, kind", [
            pytest.param((name, share), kind, id=f"{name}-{share}-{kind}")
            for name in rows for share in list(TABLE[name].shares)[:1]
            for kind in TABLE[name].head_shares]),
    "test_preset_counts_what_the_model_card_says": lambda rows: (
        "row, what", [
            pytest.param(name, what, id=f"{name}-{what}".rstrip("-"))
            for name in rows for what in TABLE[name].presets]),
    "test_the_plan_knows_the_kinds": lambda rows: (
        "row", [name for name in rows if TABLE[name].plan]),
    "test_the_cells_flops_and_bytes_against_hand_counts": lambda rows: (
        "row", [name for name in rows if TABLE[name].hand_counts]),
}


def pytest_generate_tests(metafunc):
    """The suite's tests get the rows the module that took them names in
    ``ROWS``."""
    make = _PARAMETERS.get(metafunc.function.__name__)
    if make is None or metafunc.function.__module__ != __name__:
        return
    names, values = make(metafunc.module.ROWS)
    metafunc.parametrize(names, values,
                         indirect=["case"] if "case" in names else [])


def tests_of(rows):
    """What a ``tests/test_<model>.py`` puts into its globals: the fixture,
    the hook above and every test that one of ``rows`` takes (a test with
    no parameters would be collected as one skip)."""
    return {"pytest_generate_tests": pytest_generate_tests, "case": case,
            **{name: globals()[name] for name, make in _PARAMETERS.items()
               if make(rows)[1]}}


@pytest.fixture(scope="module")
def case(request):
    """The module's one ``Case`` of this (row, share): pytest orders a
    module's tests by the position of a parameter in its list, not by its
    value, so a fixture that built a case whenever the parameter changes
    would build each several times."""
    made = request.module.__dict__.setdefault("_cases_made", {})
    if request.param not in made:
        made[request.param] = Case(*request.param)
    return made[request.param]


def _group(tree, group):
    if group == "top":
        return {k: v for k, v in tree.items() if k not in ("layers", "mtp")}
    return tree["mtp"] if group == "mtp" else tree["layers"][group]


def _report(said, key):
    """What a row's ``state`` names of the layers' reports: all of them
    (None), one name, or a path of names."""
    for name in () if key is None else (key,) if isinstance(key, str) else key:
        said = said[name]
    return said


def _flat(tree):
    return {jax.tree_util.keystr(path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


# ---- the program against the reference


def _routers_match(case, router):
    """The routed layers' router logits and choices against the
    reference's ``token_nll``."""
    row, cfg, want = case.row, case.cfg, case.want
    if "router" in row.reports:
        assert router["logits"].shape == want["router_logits"].shape
        assert router["logits"].shape[1:] == (
            case.inputs.size, cfg.num_experts)
        np.testing.assert_allclose(np.asarray(router["logits"]),
                                   want["router_logits"], rtol=1e-5,
                                   atol=row.reports["router"])
    if "choice" not in row.reports:
        assert "chosen" not in router       # no bias or limit: the top k
        return
    chosen = np.asarray(case.forced["forced_topk"] if case.forced
                        else router["chosen"])          # route's own
    assert chosen.shape == want["chosen"].shape == (
        router["logits"].shape[0], case.inputs.size, cfg.top_k)
    assert (np.sort(chosen, -1) == np.sort(want["chosen"], -1)).all()
    if "moved" in row.reports:
        # the bias, or the group limit, moved some choice away from the
        # largest scores
        plain = np.argsort(-want["router_logits"], -1)[..., :cfg.top_k]
        assert (np.sort(plain, -1) != np.sort(chosen, -1)).any()


def test_output_matches_the_reference(case):
    """``tiny()`` is what its docstring says; logits and what the layers
    report (the routed layers' router logits and choices; the scan's and
    the rule's last states) against the plain float32 reference on seeded
    weights, at the row's tolerance."""
    row = case.row
    row.says(case.cfg, case.params)
    logits, said = case.program
    np.testing.assert_allclose(np.asarray(logits), case.want_logits,
                               rtol=row.logits_tol[0], atol=row.logits_tol[1])
    if {"router", "choice"} & set(row.reports):
        _routers_match(case, said.get("router", said))
    if "state" in row.reports:
        key, _, shape = row.state
        states = _report(said, key)
        want = case.want["last_states"]
        assert states.shape == want.shape == (
            3, case.tokens.shape[0]) + shape(case.cfg)
        np.testing.assert_allclose(np.asarray(states), want, rtol=1e-5,
                                   atol=row.reports["state"])
    if row.reports_also:
        row.reports_also(case)


def test_each_term_of_the_loss_matches_the_reference(case):
    """The loss and each of its terms, the routed layers' counts over all
    the experts (held or not) and the rows held, the per-position loss
    through the blocked head and the state's counter."""
    row, mod, cfg = case.row, case.mod, case.cfg
    loss, terms = case.loss_terms
    want = row.want_terms(case) if row.want_terms else case.want["terms"]
    for name, value in want.items():
        if name not in terms and name != "loss":
            assert value == 0.0, name       # a term this model has not
            continue
        got = float(loss if name == "loss" else terms[name])
        rtol, atol = row.term_tol.get(name, row.term_tol[""])
        assert abs(got - float(value)) <= max(
            atol, rtol * abs(float(value))), (name, got, value)
    if "counts" in row.reports:
        E = cfg.num_experts
        counts = np.stack([np.bincount(c.ravel(), minlength=E)
                           for c in case.want["chosen"]])
        assert (np.asarray(terms["expert_counts"]) == counts).all()
        assert int(counts.sum()) == len(counts) * case.inputs.size * cfg.top_k
        first, held = cfg.experts_held or (0, E)
        assert int(mod.rows_held(cfg, terms["expert_counts"])) == int(
            counts[:, first:first + held].sum())
    if "state" in row.reports:
        key, counter, _ = row.state
        nll, again = case.token_nll
        np.testing.assert_allclose(np.asarray(nll), case.want["nll"],
                                   rtol=1e-5, atol=row.reports["state"])
        said = case.program[1]
        np.testing.assert_array_equal(np.asarray(_report(said, key)),
                                      np.asarray(_report(again, key)))
        np.testing.assert_allclose(float(terms[counter]),
                                   case.want["state_abs_max"], rtol=1e-5)
        assert case.want["state_abs_max"] == np.abs(
            case.want["last_states"]).max() > 0
    if row.terms_also:
        row.terms_also(case, loss, terms)


def test_every_leafs_gradient_matches_the_reference(case, group):
    """Every leaf's gradient of the whole loss, its router term with it,
    against the reference's: the leaves above the stack (``top``), then a
    kind's. A bias no optimizer owns has none."""
    rtol, atol, floor, reached = case.row.grad_tol
    got, want = (_flat(_group(g, group)) for g in case.gradients)
    seen = 0
    for path, w in want.items():
        if path.endswith("['router_bias']"):
            assert path not in got or not np.asarray(got[path]).any()
            continue
        seen += 1
        if case.row.grad_l2 is not None:
            g, w = (np.asarray(a, np.float64) for a in (got[path], w))
            gap = np.sqrt(np.square(g - w).sum() / np.square(w).sum())
            assert gap < case.row.grad_l2, (path, gap)
            continue
        scale = float(jnp.abs(w).max())
        assert scale > reached, path                    # it is reached
        np.testing.assert_allclose(
            np.asarray(got[path]), np.asarray(w), rtol=rtol,
            atol=atol * max(scale, floor), err_msg=path)
    assert seen == case.row.groups[group]
    assert set(got) - set(want) == set()


def test_reference_gradient_of_a_weighted_loss(case):
    """What the chip check compares: ``token_nll(grad_weights=...)`` gives
    the gradient of ``sum(weights * per-position loss)`` for the first
    layer of each kind and the leaves above the stack, on the program's
    choices where a bias takes part in them; the program's own gradient of
    that scalar, through the blocked head, agrees, and the rest of
    ``token_nll``'s result is what it is without the gradient."""
    row, mod, ref, cfg = case.row, case.mod, case.ref, case.cfg
    weights = np.random.default_rng(row.weighted).uniform(
        0.5, 1.5, (2, 32)).astype(np.float32) / 64
    _, got = case.weighted_gradient(weights)
    want = ref.token_nll(cfg, case.params, case.tokens, grad_weights=weights,
                         **case._forced("weighted"))
    np.testing.assert_allclose(want["nll"], case.want["nll"], atol=1e-6)
    assert set(want["grads"]) == set(case.params)
    assert set(want["grads"]["layers"]) == set(row.groups) - {"top"}
    got, want = _flat(ref.first_layers(got)), _flat(want["grads"])
    assert len(want) == sum(row.groups.values())
    rtol, atol, _, _ = row.grad_tol
    for path, w in want.items():
        assert "router_bias" not in path
        assert got[path].shape == w.shape
        scale = float(jnp.abs(w).max())
        assert scale > 1e-6, path
        np.testing.assert_allclose(np.asarray(got[path]), np.asarray(w),
                                   rtol=rtol, atol=atol * max(scale, 1e-4),
                                   err_msg=path)


@pytest.mark.parametrize("masked", [False, True], ids=["mean", "masked"])
def test_training_loss_is_the_weighted_mean_of_token_nll(case, masked):
    """The timed path held to the path the cell's check differentiates:
    ``loss_terms`` (``blocked_head_loss``, whose rule takes a block's
    gradients in the forward) and the same weighted mean of
    ``token_nll``'s positions (the checkpointed rows) give one loss and,
    leaf by leaf, one gradient, with a mask that zeroes positions and
    without."""
    mod, cfg, params = case.mod, case.cfg, case.params
    tokens = jnp.asarray(case.tokens)
    mask = (np.random.default_rng(5).uniform(size=(2, 33)) < 0.6).astype(
        np.float32) if masked else np.ones((2, 33), np.float32)
    # the rows' weighted sum is the case's one function of the weights
    want, want_g = case.weighted_gradient(
        mask[:, 1:] / max(mask[:, 1:].sum(), 1))
    if masked:
        with jax.default_matmul_precision("highest"):
            got, got_g = jax.jit(jax.value_and_grad(lambda p: mod.loss_fn(
                cfg, p, {"tokens": tokens, "mask": jnp.asarray(mask)})))(
                    params)
    else:                   # the case's own: the loss without a mask
        (got, _), got_g = case._loss_and_gradient
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    flat, _ = jax.tree_util.tree_flatten_with_path(got_g)
    assert len(flat) == sum(case.row.groups.values())
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want_g)):
        scale = float(jnp.abs(w).max())
        assert scale > 1e-6, path
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5,
                                   atol=1e-6 * max(scale, 1e-2),
                                   err_msg=str(path))


@pytest.mark.parametrize("how", ["ramp", "constant-rate", "unchanged"])
def test_first_step_against_the_reference_adamw(case, how):
    """What the cell's check holds the update to: the first moment and the
    parameters its own train step hands on, against optax's adamw in
    float32 on the reference's gradient of the mean loss. At the foot of
    a ramp the rate is 0 and the parameters come out bit-equal; at a
    constant rate they move as the reference's do; a step that hands on
    what it was given reads 1 on the moment."""
    import optax

    mod, ref_mod, cfg, params = case.mod, case.ref, case.cfg, case.params
    near = case.row.blocked["step_tol"]
    cell = import_module("benchmark.cells." + case.row.blocked["cell"])
    tokens = np.asarray(case.tokens, np.int32)
    tx = optax.adamw(1e-3 if how == "constant-rate"
                     else optax.linear_schedule(0.0, 1e-4, 2000))
    with jax.default_matmul_precision("highest"):
        # one step a schedule: "unchanged" reads the ramp's
        stepped = case.__dict__.setdefault("first_steps", {})
        if (how == "constant-rate") not in stepped:
            stepped[how == "constant-rate"] = jax.jit(cell.make_step(
                mod, cfg, tx))(params, tx.init(params), {"tokens": tokens})
        after, opt, loss, counter = stepped[how == "constant-rate"]
        left = cell.first_step_left(ref_mod, after, opt)
        if how == "unchanged":
            left = {"params": jax.device_get(ref_mod.first_layers(params)),
                    "mu": jax.tree_util.tree_map(np.zeros_like, left["mu"])}
        gaps = cell.compare(mod, ref_mod, cfg, params, jnp.asarray(tokens),
                            tokens, first_step=(tx, left))
    moment = [v for leaves in gaps["first_step"]["moment_gap"].values()
              for v in leaves.values()]
    assert len(moment) == sum(case.row.groups.values())
    assert set(gaps["gradient_gap"]) == set(case.row.groups)
    if how == "unchanged":
        assert all(v == 1.0 for v in moment)
    else:
        assert max(moment) < near
    if how == "constant-rate":
        moved = float(jnp.abs(after["embed"] - params["embed"]).max())
        assert 5e-4 < moved < 2e-3                  # one step at 1e-3
        assert gaps["first_step"]["param_gap"] < 1e-6
    else:
        assert gaps["first_step"]["param_gap"] == 0.0
    assert gaps["state_head_gap"]["worst"] < near
    assert float(counter) == pytest.approx(
        gaps["state_abs_max"]["reference"], rel=1e-5)


def test_variants_agree(case, what):
    """Full remat, the unrolled layer loop and another chunk of the rule
    compute what the scanned stack without remat does (at a chunk of 8);
    in bf16 the loss stays near float32's."""
    mod, cfg, params, tokens = case.mod, case.cfg, case.params, case.tokens
    base_loss = float(case.loss_terms[0])
    other = {"remat-full": lambda: replace(cfg, remat=True,
                                           remat_policy="full"),
             "unrolled": lambda: replace(cfg, scan_layers=False),
             "bf16": lambda: replace(cfg, dtype=jnp.bfloat16),
             "chunk-4": lambda: replace(cfg, rule_chunk=4),
             "chunk-16": lambda: replace(cfg, rule_chunk=16)}[what]()
    loss, grads = jax.jit(jax.value_and_grad(lambda p: mod.loss_fn(
        other, p, {"tokens": tokens})))(params)
    assert abs(float(loss) - base_loss) < (
        5e-2 if what == "bf16" else 1e-5)
    assert all(bool(jnp.isfinite(g).all())
               for g in jax.tree_util.tree_leaves(grads))


def test_fsdp_train_step_matches_unsharded(case):
    """``param_shardings`` on an fsdp mesh: the loss and an adamw step's
    parameters agree with one device's."""
    import optax

    mod, cfg, params = case.mod, case.cfg, case.params
    tokens = jnp.asarray(np.concatenate([case.tokens, case.tokens]))
    mesh = build_mesh(MeshSpec({"fsdp": 4}), devices=jax.devices()[:4])
    tx = optax.adamw(1e-3)

    def step(p, opt, mesh_):
        loss, grads = jax.value_and_grad(lambda q: mod.loss_fn(
            cfg, q, {"tokens": tokens}, mesh=mesh_))(p)
        updates, opt = tx.update(grads, opt, p)
        return optax.apply_updates(p, updates), loss

    want_p, want = jax.jit(lambda p, o: step(p, o, None))(
        params, tx.init(params))
    sharded = jax.device_put(params, mod.param_shardings(cfg, mesh))
    got_p, got = jax.jit(lambda p, o: step(p, o, mesh))(
        sharded, tx.init(sharded))
    assert abs(float(got) - float(want)) < 1e-5
    # adamw's first step is the rate times the gradient's sign, nearly: an
    # entry whose gradient is within a rounding of zero may move by a part
    # of 1e-3 more or less (one of Olmo-Hybrid's 75,264 did, by 1.7e-4)
    for a, b in zip(jax.tree_util.tree_leaves(got_p),
                    jax.tree_util.tree_leaves(want_p)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=case.row.blocked["fsdp_atol"])


# ---- the shares: what a chip holds of a layer adds up to the layer


def test_expert_shares_add_up_to_the_uncut_layer(row):
    """As many chips as shares, each with its experts of one routed layer
    (one of twenty, one of sixteen, four of sixteen): what their layers add
    to the residual stream (the part's body less its input), with what
    every chip computes alike (the shared expert) counted once, is the
    uncut reference's layer, the reference's own shares add up to it too,
    and ``experts_held=None`` is that sum."""
    row = TABLE[row]
    how = row.expert_shares
    mod = import_module("ray_tpu.models." + row.name)
    ref = import_module("benchmark.references." + row.name + "_ref")
    cfg = getattr(mod, row.config).tiny(**how["tiny"])
    params = mod.init_params(cfg, jax.random.PRNGKey(2))
    p = {k: v[0] for k, v in params["layers"][how["kind"]].items()}
    if "bias" in how:
        p["router_bias"] = how["bias"] * jax.random.normal(
            jax.random.PRNGKey(3), p["router_bias"].shape)
    if "moved" in how:
        leaf, far = how["moved"]
        p[leaf] = far * jax.random.normal(jax.random.PRNGKey(3),
                                          p[leaf].shape)
    mlp = mod.LAYER_KINDS[how["kind"]][-1]
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 48, 64))
    u = how["norm"](cfg, p, x)[0]
    uncut = how.get("uncut", lambda ref, cfg, p, u: ref.routed_layer(
        cfg, p, u))
    want = uncut(ref, cfg, p, u)
    # (a model without a shared expert: nothing is computed alike)
    shared = how["shared"](ref, cfg, p, u) if "shared" in how else 0.0 * want
    assert "shared" not in how or float(jnp.abs(shared).max()) > 1e-3
    ctx = Ctx(None, {})
    held_leaves = [n for n in ("e_gate", "e_up", "e_down") if n in p]
    total = ref_total = shared
    for first in range(0, cfg.num_experts, how["each"]):
        mine = {**p, **{n: p[n][first:first + how["each"]]
                        for n in held_leaves}}
        held = replace(cfg, experts_held=(first, how["each"]))
        with jax.default_matmul_precision("highest"):
            out, said = mlp.body(held, x, mine, ctx)
        assert int(said["router"]["counts"].sum()) == 48 * cfg.top_k
        total = total + (out - x)[0] - shared
        if "uncut" not in how:
            ref_total = ref_total + ref.routed_layer(held, mine, u,
                                                     shared=False)
    rtol, atol = how.get("tol", (1e-4, 1e-5))
    np.testing.assert_allclose(total, want, rtol=rtol, atol=atol)
    if "uncut" not in how:
        np.testing.assert_allclose(ref_total, want, rtol=rtol, atol=atol)
    with jax.default_matmul_precision("highest"):
        whole, _ = mlp.body(cfg, x, p, ctx)
    np.testing.assert_allclose((whole - x)[0], want, rtol=rtol, atol=atol)


def test_head_shares_add_up_to_the_whole_layers_attention(case, kind):
    """One head a chip (an index, where the layer has one, whole on every
    chip: it is not divided, so every chip chooses the same keys): what
    the blocks add to the residual stream sums to the whole layer's,
    program and reference alike."""
    from ray_tpu.ops import mla

    row, cfg, ref = case.row, case.cfg, case.ref
    heads, prefix = row.head_shares[kind]
    part = case.mod.LAYER_KINDS[kind][0]
    p = {k: v[0] for k, v in case.params["layers"][kind].items()}
    tokens = case.inputs[:1]
    x = jax.random.normal(jax.random.PRNGKey(5), (1, tokens.shape[1], 64))
    sz = mla.sizes(cfg, prefix)
    dn, dr, dv = sz.d_n, sz.d_r, sz.d_v

    def block(cfg_):
        """What the part adds under ``cfg_``, compiled once."""
        def added(p_):
            ctx = Ctx(None, {part.once: part.once(cfg_, tokens)})
            return part.body(cfg_, x, p_, ctx)[0] - x

        def run(p_, f=jax.jit(added)):
            with jax.default_matmul_precision("highest"):
                return f(p_)
        return run

    whole = block(cfg)(p)
    held = replace(cfg, **{prefix + "num_heads": 1,
                           prefix + "heads_of": heads})
    one_head = block(held)
    np.testing.assert_allclose(
        whole[0], row.ref_attention(ref, cfg, p, x[0], kind), rtol=1e-4,
        atol=1e-5)
    parts, ref_parts = [], []
    for head in range(heads):
        mine = {**p,
                "wq_b": p["wq_b"][:, head * (dn + dr):(head + 1) * (dn + dr)],
                "wkv_b": p["wkv_b"][:, head * (dn + dv):
                                    (head + 1) * (dn + dv)],
                "wo": p["wo"][head * dv:(head + 1) * dv]}
        if "wg" in p:
            mine["wg"] = p["wg"][:, head:head + 1]
        want = {n: leaf.shape for n, leaf in part.leaves(held).items()}
        assert want == {n: mine[n].shape for n in want}
        parts.append(one_head(mine))
        ref_parts.append(row.ref_attention(ref, held, mine, x[0], kind))
    np.testing.assert_allclose(sum(parts), whole, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(sum(ref_parts), whole[0], rtol=1e-4,
                               atol=1e-5)
    assert float(jnp.abs(parts[0] - parts[1]).max()) > 1e-3


# ---- the preset, the plan and the cell's arithmetic


def test_preset_counts_what_the_model_card_says(row, what):
    """The published config's layers and parameters, and the cell's cut of
    it (Laguna's 117.6 B: ``tests/test_layer_patterns.py``)."""
    TABLE[row].presets[what](import_module("ray_tpu.models." + row))


def test_the_plan_knows_the_kinds(row):
    """``describe_stack`` on the row's table in bf16: the runs of one
    kind, what each kind keeps on the ladder's rungs, a level a kind from
    ``remat_plan``, and a kind the table has not is refused."""
    row = TABLE[row]
    mod = import_module("ray_tpu.models." + row.name)
    T = row.plan["tokens"]
    cfg = getattr(mod, row.config).tiny(
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, **row.plan["tiny"])
    params = jax.eval_shape(lambda k: mod.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    stack = llama.describe_stack(cfg, mod.LAYER_KINDS, params["layers"], T,
                                 pattern=cfg.pattern, head_tokens=T)
    assert stack["runs"] == row.plan["runs"]
    kinds = stack["kinds"]
    assert set(kinds) == set(row.groups) - {"top"}
    row.plan["rungs"](kinds)
    plan = llama.remat_plan(cfg, stack, T, 10 ** 6, 10 ** 9, False)
    assert set(plan["level"]) == set(kinds)
    with pytest.raises(ValueError, match="does not know the layer kind"):
        llama.describe_stack(cfg, mod.LAYER_KINDS,
                             {"mamba": params["layers"][cfg.pattern[-1]]}, T,
                             pattern=("mamba",))


def test_the_cells_flops_and_bytes_against_hand_counts(row):
    """The cell's FLOPs and bytes library on the configuration file,
    against counts written out by hand: no roofline or MFU counts more
    than the mathematics needs."""
    TABLE[row].hand_counts()
