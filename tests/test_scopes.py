"""The scope registry (ISSUE 62): ``observability/scopes.py`` names the
family of every scope the program opens, and ``device.py:scope_mix``
names the fusions that hold two families' work. Hand-made ``op_name``s
and hand-written HLO text; nothing is compiled."""

import ast
import itertools
import json
import os

import pytest

from elasticdl_tpu.observability import device as device_obs
from elasticdl_tpu.observability import scopes

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGES = ("models", "ops", "train")

FWD = "jit(train_step)/jvp(forward)/M/"
BWD = "jit(train_step)/transpose(jvp(forward))/M/"
REMAT = BWD + "jvp(forward)/M/checkpoint/"


@pytest.mark.parametrize("op_name, expected", [
    ("jit(train_step)/jvp(forward)/TransformerLM/block_3/mlp_up/"
     "dot_general", ("forward/TransformerLM/block_*/mlp_up", "forward")),
    ("jit(train_step)/transpose(jvp(forward))/TransformerLM/block_0/attn/"
     "out_proj/dot_general",
     ("forward/TransformerLM/block_*/attn/out_proj", "backward")),
    ("jit(train_step)/transpose(jvp(forward))/TransformerLM/jvp(forward)/"
     "TransformerLM/checkpoint/block_7/attn/value/dot_general",
     ("forward/TransformerLM/block_*/attn/value", "recompute")),
    ("jit(train_step)/jvp(forward)/M/rematted_computation/block_1/mul",
     ("forward/M/block_*", "recompute")),
    ("jit(train_step)/jvp(forward)/TransformerLM/wte/jit(_take)/gather",
     ("forward/TransformerLM/wte", "forward")),
    ("jit(train_step)/optimizer/add", ("optimizer", "forward")),
    ("jit(train_step)/transpose(jvp(loss))/mul", ("loss", "backward")),
    ("jit(train_step)/jvp()/max", ("unscoped", "forward")),
])
def test_op_scope_is_what_it_was_before_the_move(op_name, expected):
    assert scopes.op_scope(op_name) == expected
    # the memory walk and its tests find it where it was
    assert device_obs.op_scope is scopes.op_scope


@pytest.mark.parametrize("op_name, expected", [
    (FWD + "block_3/dense_mlp/mlp_up/dot_general",
     ("mlp", "dense_mlp", "forward")),
    (FWD + "block_0/attn/mla/q_proj/q_proj/dot_general",
     ("attention", "mla/q_proj", "forward")),
    # a container is no family: the expert layer inside ``mtp/block``
    (BWD + "mtp/block/mtp_block/moe_mlp/moe/experts/dot_general",
     ("mlp", "moe/experts", "backward")),
    (FWD + "looped/pass/block_1/attn/attn_full/qkv/query/dot_general",
     ("attention", "attn_full/qkv", "forward")),
    # a scope of two names beats the one name it ends in
    (FWD + "mtp/head/lm_head/dot_general",
     ("head_loss", "mtp/head", "forward")),
    (FWD + "head/lm_head/dot_general", ("head_loss", "head", "forward")),
    (FWD + "looped/exit_norm/ln_f/mul",
     ("head_loss", "looped/exit_norm", "forward")),
    # the loss's own scope inside ``loss``: the deepest decides
    ("jit(train_step)/jvp(loss)/exit/head/dot_general",
     ("head_loss", "exit/head", "forward")),
    ("jit(train_step)/transpose(jvp(loss))/sub",
     ("head_loss", "loss", "backward")),
    (FWD + "block_2/residual/norm/ln_attn/mul",
     ("residual", "residual/norm", "forward")),
    (FWD + "block_2/hc_attn/mhc/pre/mul", ("residual", "mhc/pre", "forward")),
    (FWD + "block_2/attn/mamba/scan/while/body/dot_general",
     ("mixer", "mamba/scan", "forward")),
    (FWD + "bd/noise/select_n", ("objective", "bd/noise", "forward")),
    ("jit(train_step)/optimizer/add", ("step", "optimizer", "forward")),
    ("jit(train_step)/transpose(jvp(cast_params))/convert_element_type",
     ("step", "cast_params", "backward")),
    ("jit(train_step)/health/reduce_sum", ("step", "health", "forward")),
    # the forward run again inside the backward, and the rematerialised
    # block's backward proper (``op_scope`` says recompute of both)
    (REMAT + "rematted_computation/block_5/dense_mlp/mlp_up/dot_general",
     ("mlp", "dense_mlp", "recompute")),
    (REMAT + "block_5/dense_mlp/mlp_up/dot_general",
     ("mlp", "dense_mlp", "backward")),
    # the trace's ``tf_op`` ends in a colon
    (FWD + "block_3/dense_mlp/mlp_up/dot_general:",
     ("mlp", "dense_mlp", "forward")),
    # no registered scope on the path
    (FWD + "block_3/attn/query/dot_general",
     ("unnamed", "forward/M/block_*/attn/query", "forward")),
    # a module that merely has a scope's word in its name is not it
    (FWD + "loss_proj/mul", ("unnamed", "forward/M/loss_proj", "forward")),
    ("", ("unnamed", "unscoped", "forward")),
])
def test_the_deepest_registered_scope_decides(op_name, expected):
    assert scopes.family(op_name) == expected


@pytest.mark.parametrize("kernel, op_name, expected", [
    # a backward under a custom_vjp lost the forward's scope
    ("flash_bwd/bf16,bf16,bf16", BWD + "block_1/attn/flash_bwd/pallas_call",
     ("attention", "attn_full/flash", "backward")),
    ("flash_band_fwd/bf16,f32", FWD + "block_1/attn/pallas_call",
     ("attention", "attn_window/flash", "forward")),
    ("flash_sparse_bwd/bf16", BWD + "x/pallas_call",
     ("attention", "dsa/attend", "backward")),
    ("rotary_fwd/bf16,bf16", FWD + "attn/jit(rotary_fwd)/pallas_call",
     ("attention", "attn_full/rotary", "forward")),
    ("dsa_mask/s8", "", ("attention", "dsa/scores", "forward")),
    ("gdn_scan_bwd/f32", BWD + "x/pallas_call",
     ("mixer", "gdn/scan", "backward")),
    ("kda_prepare_fwd/bf16", "", ("mixer", "kda/scan", "forward")),
    ("ssd_chunk/f32", "", ("mixer", "mamba/scan", "forward")),
    ("short_conv_bwd/bf16", "", ("mixer", "short_conv/gate", "forward")),
    ("gmm/bf16", "", ("mlp", "moe/experts", "forward")),
    ("tgmm/bf16", "", ("mlp", "moe/experts", "forward")),
    ("mhc_pre_bwd/bf16,f32,f32", "", ("residual", "mhc/pre", "forward")),
    ("mhc_post_fwd/bf16", "", ("residual", "mhc/post", "forward")),
    # a scope on the path comes first: the convolution's kernels hold no
    # family's word on purpose and lie under the caller's scope
    ("qkv_conv_bwd/bf16", BWD + "attn/kda/conv/pallas_call",
     ("mixer", "kda/conv", "backward")),
    ("rotary_fwd/bf16,bf16", FWD + "attn/attn_window/rotary/pallas_call",
     ("attention", "attn_window/rotary", "forward")),
    # one kernel pair, three scopes (PR 65): ``KERNELS`` has no entry
    # for the gated norm's, the caller's scope alone decides
    ("gated_norm_bwd/bf16,bf16,f32,bf16",
     BWD + "block_2/attn/mamba/out_norm/mamba/out_norm/"
     "jit(gated_norm_bwd)/gated_norm_bwd/pallas_call",
     ("mixer", "mamba/out_norm", "backward")),
    ("gated_norm_fwd/bf16,bf16,f32", FWD + "attn/gdn/out_norm/"
     "jit(gated_norm_fwd)/gated_norm_fwd/pallas_call",
     ("mixer", "gdn/out_norm", "forward")),
    ("gated_norm_bwd/bf16,bf16,f32,bf16",
     BWD + "attn/kda/out_norm/gated_norm_bwd/pallas_call",
     ("mixer", "kda/out_norm", "backward")),
    ("gated_norm_fwd/bf16", FWD + "x/pallas_call",
     ("unnamed", "forward/M/x", "forward")),
    ("some_other_kernel/f32", FWD + "x/pallas_call",
     ("unnamed", "forward/M/x", "forward")),
])
def test_a_kernel_s_name_decides_where_the_scope_was_lost(
        kernel, op_name, expected):
    assert scopes.family(op_name, kernel) == expected


def test_every_family_s_scope_is_known_and_containers_have_no_family():
    for family, names in scopes.FAMILIES.items():
        for scope in names:
            probe = scope + "part" if scope.endswith("/") else scope
            assert scopes.known(probe) and scopes.family_of(probe) == family
    for scope in scopes.CONTAINERS:
        assert scopes.known(scope) and scopes.family_of(scope) is None
        assert scopes.family(FWD + scope + "/add")[0] == scopes.UNNAMED
    for scope in scopes.KERNELS.values():
        assert scopes.family_of(scope) is not None, scope
    assert not scopes.known("attn") and not scopes.known("block_3")


# ---------------------------------------------------------------------
# every ``jax.named_scope`` of the program answers to the registry


def _trees():
    for package in PACKAGES:
        base = os.path.join(REPO, "elasticdl_tpu", package)
        for name in sorted(os.listdir(base)):
            if name.endswith(".py"):
                path = os.path.join(base, name)
                with open(path) as f:
                    yield os.path.relpath(path, REPO), ast.parse(f.read())


class _Resolver:
    """The strings a ``jax.named_scope`` argument can be, from the
    ASTs alone: a literal; a module's constant; a ``"%s/%s" % (...)``
    over what its parts can be; ``kind_scope`` is one of the registry's
    ``attn_`` kinds; a function's parameter is its default, or what a
    call of that function (or any call, under the parameter's name as
    a keyword) passes as a literal."""

    def __init__(self, trees):
        self.trees = trees
        self.calls = [
            (tree, node) for _, tree in trees for node in ast.walk(tree)
            if isinstance(node, ast.Call)]
        self.functions = [
            (tree, node) for _, tree in trees for node in ast.walk(tree)
            if isinstance(node, ast.FunctionDef)]

    def constants(self, tree):
        return {
            target.id: node.value.value
            for node in tree.body if isinstance(node, ast.Assign)
            and isinstance(node.value, ast.Constant)
            and isinstance(node.value.value, str)
            for target in node.targets if isinstance(target, ast.Name)}

    def parameter(self, name):
        """Over EVERY function with a parameter of this name (a
        ``custom_vjp``'s backward takes its scope from the forward's
        signature): its default and what calls of it pass."""
        found = set()
        for tree, function in self.functions:
            names = [a.arg for a in function.args.args]
            if name not in names:
                continue
            args = function.args
            for arg, default in zip(
                    reversed(args.args), reversed(args.defaults)):
                if arg.arg == name:
                    found |= self.values(default, None, tree)
            # a method's ``self`` is not among a call's arguments
            at = names.index(name) - (names[0] == "self")
            for call_tree, call in self.calls:
                callee = getattr(
                    call.func, "attr", getattr(call.func, "id", ""))
                if callee != function.name:
                    continue
                if 0 <= at < len(call.args):
                    found |= self.values(call.args[at], None, call_tree)
                for keyword in call.keywords:
                    if keyword.arg == name:
                        found |= self.values(keyword.value, None, call_tree)
        return found

    def values(self, node, function, tree):
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            return {node.value}
        if isinstance(node, ast.Name):
            constants = self.constants(tree)
            if node.id in constants:
                return {constants[node.id]}
            if function is not None and node.id in [
                    a.arg for a in function.args.args]:
                return self.parameter(node.id)
            return set()
        if isinstance(node, ast.Attribute) and node.attr == "kind_scope":
            return {g.rstrip("/") for names in scopes.FAMILIES.values()
                    for g in names if g.startswith("attn_")}
        if isinstance(node, ast.BoolOp):
            return set().union(*(
                self.values(value, function, tree) for value in node.values))
        if (isinstance(node, ast.BinOp) and isinstance(node.op, ast.Mod)
                and isinstance(node.left, ast.Constant)):
            parts = (node.right.elts if isinstance(node.right, ast.Tuple)
                     else [node.right])
            options = [self.values(p, function, tree) for p in parts]
            return {node.left.value % combo
                    for combo in itertools.product(*options)}
        return set()


def _named_scopes():
    trees = list(_trees())
    resolver = _Resolver(trees)
    for path, tree in trees:
        functions = [n for n in ast.walk(tree)
                     if isinstance(n, (ast.FunctionDef, ast.Lambda))]
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and getattr(node.func, "attr", "") == "named_scope"):
                continue
            inside = [
                f for f in functions if isinstance(f, ast.FunctionDef)
                and f.lineno <= node.lineno <= f.end_lineno]
            function = max(inside, key=lambda f: f.lineno, default=None)
            yield path, node.lineno, resolver.values(
                node.args[0], function, tree)


NAMED_SCOPES = sorted(
    (path, line, tuple(sorted(found))) for path, line, found in
    _named_scopes())


def test_the_walk_finds_the_program_s_scopes():
    found = {scope for _, _, names in NAMED_SCOPES for scope in names}
    # one of each way a scope is written today
    assert {"forward", "optimizer", "cast_params", "dense_mlp",
            "mla/q_proj", "attn_full/qkv", "attn_window/flash",
            "gdn/conv", "kda/conv", "short_conv/gate", "bd/noise",
            "moe/exchange", "residual/norm", "looped/pass"} <= found
    assert len(NAMED_SCOPES) >= 80


@pytest.mark.parametrize(
    "path, line, names", NAMED_SCOPES,
    ids=["%s:%d" % (os.path.basename(p), n) for p, n, _ in NAMED_SCOPES])
def test_every_named_scope_is_registered(path, line, names):
    """The next architecture registers its scopes in
    ``observability/scopes.py:FAMILIES`` (or ``CONTAINERS``) or fails
    here."""
    assert names, (
        "%s:%d: the walk cannot tell what this jax.named_scope is "
        "called: write it as a literal, a module constant or a "
        "'%%s/%%s' %% (...) of those" % (path, line))
    unknown = [scope for scope in names if not scopes.known(scope)]
    assert not unknown, (
        "%s:%d opens %s: observability/scopes.py knows no such scope"
        % (path, line, unknown))


# ---------------------------------------------------------------------
# scope_mix: hand-written HLO in the TPU compiler's print


def _metadata(op_name):
    return ', metadata={op_name="%s"}' % op_name if op_name else ""


def _fused(name, interior):
    """A fused computation: ``interior`` is [(opcode, dims, op_name)],
    the last one its root."""
    lines = ["%%%s (p0: f32[8,128]) -> f32[8,128] {" % name,
             "  %%p0.%s = f32[8,128]{1,0:T(8,128)} parameter(0)" % name]
    for at, (opcode, dims, op_name) in enumerate(interior):
        lines.append(
            "  %s%%%s.%s.%d = f32[%s]{1,0:T(8,128)} %s(%%p0.%s)%s" % (
                "ROOT " if at == len(interior) - 1 else "", opcode, name,
                at, dims, opcode, name, _metadata(op_name)))
    return lines + ["}", ""]


def _module(computations, entry, bodies=()):
    """``entry``: [(instruction, computation called, op_name)]."""
    lines = ["HloModule jit_train_step, is_scheduled=true", ""]
    for name, interior in computations:
        lines += _fused(name, interior)
    for body, fusions in bodies:
        lines += ["%%%s (arg: f32[8,128]) -> f32[8,128] {" % body,
                  "  %%arg.%s = f32[8,128]{1,0:T(8,128)} parameter(0)" % body]
        lines += [_fusion(*f, operand="arg." + body) for f in fusions]
        lines += ["}", ""]
    lines += ["ENTRY %main (x: f32[8,128]) -> f32[8,128] {",
              "  %x = f32[8,128]{1,0:T(8,128)} parameter(0)"]
    lines += [_fusion(*f) for f in entry]
    for body, _ in bodies:
        lines.append(
            "  %%while.%s = f32[8,128]{1,0:T(8,128)} while(%%x), "
            "condition=%%cond, body=%%%s" % (body, body))
    return "\n".join(lines + ["}", ""])


def _fusion(instruction, called, op_name, operand="x"):
    return ("  %%%s = f32[8,128]{1,0:T(8,128)} fusion(%%%s), kind=kLoop, "
            "calls=%%%s%s" % (instruction, operand, called,
                              _metadata(op_name)))


MLP = BWD + "block_0/dense_mlp/mlp_up/dot_general"
ADAM = "jit(train_step)/optimizer/mul"
NORM = FWD + "block_0/residual/norm/ln_mlp/reduce_sum"
ROW = 8 * 128 * 4


def test_a_fusion_of_two_families_is_one_row():
    text = _module(
        [("fused_wgrad", [("convolution", "8,128", MLP),
                          ("multiply", "8,128", ADAM),
                          ("add", "8,128", ADAM),
                          # the compiler's own: no op_name, no family
                          ("copy", "8,128", None)])],
        [("fusion.7", "fused_wgrad", MLP)])
    mix = device_obs.scope_mix(text)
    assert (mix["fusions"], mix["mixed"]) == (1, 1)
    assert mix["rows"] == [{
        "op": "fusion.7", "root": "mlp",
        "bytes": {"mlp": ROW, "step": 2 * ROW},
        "heavy": {"convolution": ["mlp"]}}]
    assert mix["dropped"] == {"rows": 0, "bytes": 0}


def test_a_fusion_of_one_family_is_no_row():
    text = _module(
        [("fused_adam", [("multiply", "8,128", ADAM),
                         ("add", "8,128", ADAM)]),
         # parameters, constants and what has no op_name say nothing
         ("fused_plain", [("bitcast", "8,128", MLP),
                          ("add", "8,128", None),
                          ("reduce", "8,128", NORM)])],
        [("fusion.1", "fused_adam", ADAM), ("fusion.2", "fused_plain", NORM)])
    mix = device_obs.scope_mix(text)
    assert (mix["fusions"], mix["mixed"], mix["rows"]) == (2, 0, [])


def test_a_fusion_inside_a_while_body_counts_and_unnamed_is_a_family():
    text = _module(
        [("fused_scan", [("reduce", "8,128", NORM),
                         ("dot", "8,128", FWD + "block_0/attn/q/dot_general"),
                         ("add", "8,128", NORM)])],
        [], bodies=[("body.3", [("fusion.40", "fused_scan", NORM)])])
    mix = device_obs.scope_mix(text)
    assert (mix["fusions"], mix["mixed"]) == (1, 1)
    (row,) = mix["rows"]
    assert (row["op"], row["root"]) == ("fusion.40", "residual")
    assert row["bytes"] == {"residual": 2 * ROW, "unnamed": ROW}
    assert row["heavy"] == {"dot": ["unnamed"], "reduce": ["residual"]}


def test_the_cap_drops_the_smallest_and_says_so(monkeypatch):
    monkeypatch.setattr(device_obs, "SCOPE_MIX_MAX", 3)
    computations, entry = [], []
    for n in range(1, 7):
        # fusion n holds n rows of the optimizer's bytes beside its root
        computations.append(("fused_%d" % n, [
            ("add", "%d,128" % (8 * n), ADAM),
            ("convolution", "8,128", MLP)]))
        entry.append(("fusion.%d" % n, "fused_%d" % n, MLP))
    mix = device_obs.scope_mix(_module(computations, entry))
    assert (mix["fusions"], mix["mixed"]) == (6, 6)
    assert [row["op"] for row in mix["rows"]] == [
        "fusion.6", "fusion.5", "fusion.4"]
    assert mix["dropped"] == {"rows": 3, "bytes": (1 + 2 + 3) * ROW}


def test_a_full_table_stays_under_the_event_s_256_kb():
    families = sorted(scopes.FAMILIES) + [scopes.UNNAMED]
    row = {"op": "multiply_reduce_fusion.123456", "root": "head_loss",
           "bytes": dict.fromkeys(families, 10 ** 12),
           "heavy": dict.fromkeys(
               sorted(device_obs._HEAVY_OPCODES), families)}
    assert len(json.dumps(
        {"rows": [row] * device_obs.SCOPE_MIX_MAX})) < 256 * 1024
