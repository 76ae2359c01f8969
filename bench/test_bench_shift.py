"""The ``attn_bound_share`` reader: which branch of the additive score's
softmax shift ran, on a synthetic reduced trace and in a step compiled on
the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.metrics import attn_bound_share

#: two layers' conditionals, as a compiled step's HLO text lays them out
HLO = """\
%region_0.1 (arg.1: (f32[8,2], f32[4,2])) -> (f32[8,2]) {
  %arg.1 = (f32[8,2]{1,0}, f32[4,2]{1,0}) parameter(0)
  %gte.1 = f32[8,2]{0,1} get-tuple-element(%arg.1), index=0
  %pad_clamp.1 = s32[16]{0} fusion(%c.1), kind=kLoop, calls=%f.0, metadata={op_name="jit(step)/jvp(gat.layer0)/jvp(attn.shift)/cond/branch_0_fun/exact/pad"}
  %copy.1 = f32[8,2]{1,0} copy(%gte.1)
  %scatter_fusion.1 = f32[8,2]{1,0} fusion(%copy.1, %pad_clamp.1), kind=kLoop, calls=%f.1, metadata={op_name="jit(step)/jvp(gat.layer0)/jvp(attn.shift)/cond/branch_0_fun/exact/scatter-max"}
  ROOT %tuple.1 = (f32[8,2]{1,0}) tuple(%scatter_fusion.1)
}

%region_1.2 (arg.2: (f32[2], f32[8,2])) -> (f32[8,2]) {
  %arg.2 = (f32[2]{0}, f32[8,2]{0,1}) parameter(0)
  %gte.2 = f32[8,2]{0,1} get-tuple-element(%arg.2), index=1
  %copy.2 = f32[8,2]{1,0:T(8,128)} copy(%gte.2)
  %bound_fusion.1 = f32[8,2]{1,0} fusion(%copy.2), kind=kLoop, calls=%f.2, metadata={op_name="jit(step)/jvp(gat.layer0)/jvp(attn.shift)/cond/branch_1_fun/bound/jit(_where)/select_n"}
  ROOT %tuple.2 = (f32[8,2]{1,0}) tuple(%bound_fusion.1)
}

%region_2.3 (arg.3: (f32[8,2], f32[4,2])) -> (f32[8,2]) {
  %arg.3 = (f32[8,2]{1,0}, f32[4,2]{1,0}) parameter(0)
  %scatter_fusion.2 = f32[8,2]{1,0} fusion(%arg.3), kind=kLoop, calls=%f.3, metadata={op_name="jit(step)/jvp(gat.layer1)/jvp(attn.shift)/cond/branch_0_fun/exact/scatter-max"}
  ROOT %tuple.3 = (f32[8,2]{1,0}) tuple(%scatter_fusion.2)
}

%region_3.4 (arg.4: (f32[2], f32[8,2])) -> (f32[8,2]) {
  %arg.4 = (f32[2]{0}, f32[8,2]{1,0}) parameter(0)
  %bound_add.2 = f32[8,2]{1,0} add(%arg.4, %arg.4), metadata={op_name="jit(step)/jvp(gat.layer1)/jvp(attn.shift)/cond/branch_1_fun/bound/add"}
  ROOT %tuple.4 = (f32[8,2]{1,0}) tuple(%bound_add.2)
}

ENTRY %main.5 (p.0: f32[8,2], p.1: f32[4,2]) -> f32[8,2] {
  %cond.1 = (f32[8,2]{1,0}) conditional(%pred.1, %t.1, %t.2), branch_computations={%region_0.1, %region_1.2}, metadata={op_name="jit(step)/jvp(gat.layer0)/jvp(attn.shift)/cond"}
  %cond.2 = (f32[8,2]{1,0}) conditional(%pred.2, %t.3, %t.4), branch_computations={%region_2.3, %region_3.4}, metadata={op_name="jit(step)/jvp(gat.layer1)/jvp(attn.shift)/cond"}
}
"""


def test_branches_and_the_operations_that_count_their_runs():
    """Each conditional's two branches, their device operations in program
    order (parameters, tuples and their elements run nothing); a branch's
    runs are read from the bound's first operation with a twin of the
    same opcode and shape in the exact branch, and that twin (layer 0),
    else from each branch's first (layer 1)."""
    layers = attn_bound_share.shift_branches(HLO)
    assert [{k: [op[0] for op in ops] for k, ops in lay.items()} for lay in layers] == [
        {"exact": ["pad_clamp.1", "copy.1", "scatter_fusion.1"],
         "bound": ["copy.2", "bound_fusion.1"]},
        {"exact": ["scatter_fusion.2"], "bound": ["bound_add.2"]}]
    assert layers[0]["bound"][0] == ("copy.2", "f32[8,2]", "copy")
    assert [attn_bound_share.markers(lay) for lay in layers] == [
        {"bound": "copy.2", "exact": "copy.1"},
        {"bound": "bound_add.2", "exact": "scatter_fusion.2"}]


@pytest.mark.parametrize("ops, share", [
    ({"copy.2": 4e-6, "bound_fusion.1": 4e-6, "bound_add.2": 4e-6}, 100.0),
    ({"copy.2": 2e-6, "copy.1": 2e-6, "pad_clamp.1": 9e-6,
      "bound_add.2": 2e-6, "scatter_fusion.2": 2e-6}, 50.0),
    ({"copy.2": 1e-6, "copy.1": 3e-6, "bound_add.2": 2e-6}, 62.5),
    ({"copy.1": 2e-6, "scatter_fusion.1": 1e-3, "scatter_fusion.2": 2e-6}, 0.0),
], ids=["bound_only", "one_of_each", "split", "exact_only"])
def test_share_of_the_forwards_that_took_the_bound(ops, share):
    """Only ``bound`` events read 100%; one run of each branch a layer
    (two steps, the counted operations' time alike) 50%; a layer split
    1:3 beside one that took the bound 62.5%; only ``exact`` 0%.  Time of
    a branch's other operations does not count."""
    rec = {"trace": {"ops": {**ops, "fusion.9": 0.5}}, "hlo": HLO, "steps": 2}
    assert attn_bound_share.read(rec) == pytest.approx(share)


def test_reads_nothing_without_the_shift():
    """No trace, no conditional of the shift (the two-phase program), or
    no branch in the window: the reader returns None."""
    rec = {"trace": {"ops": {"copy.2": 1e-6}}, "hlo": HLO, "steps": 2}
    assert attn_bound_share.read({**rec, "trace": None}) is None
    assert attn_bound_share.read({**rec, "hlo": HLO.replace("attn.shift", "x")}) is None
    assert attn_bound_share.read({**rec, "trace": {"ops": {"fusion.9": 1.0}}}) is None


def test_finds_the_branches_of_a_compiled_program():
    """The additive score's forward, differentiated and compiled by XLA on
    the CPU, keeps one conditional whose branches the reader finds; the
    dot score's has none."""
    from repro.sparse import sparse_attention

    rng = np.random.default_rng(0)
    rows = jnp.asarray(np.sort(rng.integers(0, 12, 40)).astype(np.int32))
    cols = jnp.asarray(rng.integers(0, 10, 40).astype(np.int32))
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k = (jax.random.normal(kk, (n, 2, 1)) for kk, n in zip(keys, (12, 10)))
    v = jax.random.normal(keys[2], (10, 2, 3))

    def hlo(score):
        loss = lambda *a: jnp.sum(sparse_attention(  # noqa: E731
            (rows, cols, 12), *a, score=score) ** 2)
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, k, v).compile().as_text()

    (layer,) = attn_bound_share.shift_branches(hlo("additive"))
    assert layer["bound"] and layer["exact"]
    assert attn_bound_share.shift_branches(hlo("dot")) == []
