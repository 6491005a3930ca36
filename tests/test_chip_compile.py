"""The TPU v5e compiler's verdict on the Pallas kernels at their real widths,
from a sandbox with no chip: each case lowers a kernel for a DESCRIBED
v5e device (`topologies.get_topology_desc`) and compiles it with the
installed libtpu — Mosaic raises here what it would raise on the chip
(interpret mode, which every other kernel test uses, cannot: the
correlation kernel passed all of those while the chip refused it).

Rules this file keeps (on-chip-measurement guide, section 2): the topology
is described inside a module-scoped fixture that skips when it cannot be —
never at import, in a `skipif`/`parametrize` argument or in conftest, and
not autouse; shardings and shapes are built in fixtures/tests; compiles run
in the test's own process; all such tests live in this ONE file (only one
xdist worker may hold the TPU library); the persistent compile cache is off
around them (an entry compiled for an unattached chip cannot be read back).
A compile that passes is not a run: numbers on the chip come from
`chip_smoke.py`.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

BATCH = 16  # the headline batch


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu / lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    from deepof_tpu.train.warmup import one_frame_locations

    prev = (jax.config.jax_enable_compilation_cache,
            jax.config.jax_include_full_tracebacks_in_locations,
            jax.config.jax_traceback_in_locations_limit)
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    # locations as the program sets them wherever it enables its compile
    # cache, i.e. on the chip: they decide what a Mosaic call is named
    one_frame_locations()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev[0])
    jax.config.update("jax_include_full_tracebacks_in_locations", prev[1])
    jax.config.update("jax_traceback_in_locations_limit", prev[2])
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _mesh(topo, n, time=1):
    from deepof_tpu.core.config import MeshConfig
    from deepof_tpu.parallel.mesh import build_mesh

    return build_mesh(MeshConfig(time=time), devices=list(topo.devices[:n]))


def _compiled_text(fn, *args, kernels=()):
    """Compile for the described chip. `kernels`: the `name=` of each
    `pallas_call` inside, which the lowering must carry as `kernel_name`
    and the compiled HLO as the custom call's instruction name — the name
    a profile's event then starts with (`%warp_fwd.1 = ... custom-call`)."""
    lowered = jax.jit(fn).lower(*args)
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the compiled text"
    if kernels:
        assert set(re.findall(r'kernel_name\s*=\s*"([^"]+)"',
                              lowered.as_text())) == set(kernels)
        calls = [ln.strip() for ln in text.splitlines()
                 if "custom-call(" in ln and "tpu_custom_call" in ln]
        assert {re.match(r"(?:ROOT )?%([A-Za-z_]+)", c).group(1)
                for c in calls} == set(kernels), calls
    return text


@pytest.mark.parametrize("hw", [(80, 112), (40, 56), (160, 224), (112, 240)])
@pytest.mark.parametrize("which", ["fwd", "flow_grad"])
def test_warp_kernels_compile_for_v5e(one_chip, which, hw):
    """Both warp kernels at pyramid levels `warp_impl=auto` admits: two of
    one lane tile (W <= 128) and the finest of a 320x448 input, two tiles
    (160x224), with the Sintel crop's (112x240): B16, C3, f32. Each holds
    a vector-to-scalar min/max (the sweep's bounds) and a loop whose trip
    count is data."""
    from deepof_tpu.ops.pallas.warp import (_pallas_warp_flow_grad,
                                            _pallas_warp_fwd)

    h, w = hw
    img = jax.ShapeDtypeStruct((BATCH, h, w, 3), jnp.float32, sharding=one_chip)
    flow = jax.ShapeDtypeStruct((BATCH, h, w, 2), jnp.float32,
                                sharding=one_chip)
    if which == "fwd":
        _compiled_text(lambda im, fl: _pallas_warp_fwd(im, fl, False),
                       img, flow, kernels=["warp_fwd"])
    else:
        _compiled_text(lambda im, fl, ct: _pallas_warp_flow_grad(
            im, fl, ct, False), img, flow, img, kernels=["warp_flow_grad"])


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("hw", [(40, 56), (48, 64), (28, 60)])
@pytest.mark.parametrize("which", ["fwd", "bwd"])
def test_corr_kernel_compiles_for_v5e(one_chip, which, hw, dtype):
    """FlowNet-C's cost volume at its real shapes: conv3 features of
    320x448 and 384x512 inputs and of the Sintel crop 224x480 (1/8
    resolution; W 60 is no multiple of 16), C=256, max_disp=20, stride=2,
    the forward kernel (the image's phase-split padded f2 resident in
    VMEM, the products on the MXU, the diagonals turned to their lanes by
    a strided roll) and the backward, its transpose (f2's layout and the
    float32 df2 accumulator in it resident in VMEM, the cotangent's
    blocks placed by a strided roll, the same products transposed, f1
    transposed in VMEM). Mosaic refuses a sublane slice it cannot prove
    tile-aligned ('cannot statically prove that index in dimension 1 is a
    multiple of 8') and a strided load or store of 16-bit data."""
    from deepof_tpu.ops.pallas.corr import _pallas_corr_bwd, _pallas_corr_fwd

    h, w = hw
    f = jax.ShapeDtypeStruct((BATCH, h, w, 256), dtype, sharding=one_chip)
    if which == "fwd":
        _compiled_text(lambda a, b: _pallas_corr_fwd(a, b, 20, 2, False),
                       f, f, kernels=["corr_fwd"])
        return
    g = jax.ShapeDtypeStruct((BATCH, h, w, 441), dtype, sharding=one_chip)
    _compiled_text(lambda a, b, ct: _pallas_corr_bwd(a, b, ct, 20, 2, False),
                   f, f, g, kernels=["corr_bwd"])


def test_corr_forward_writes_the_models_layout(one_chip):
    """The public forward at the cell's shapes compiles to the kernel and
    the pad of f2 before it, and nothing after it: the kernel writes the
    (B, H, W, 441) volume in bfloat16 itself, so no transpose, copy or
    convert of a 441-wide volume follows `corr_fwd`."""
    from deepof_tpu.ops.pallas.corr import correlation_pallas

    f = jax.ShapeDtypeStruct((BATCH, 48, 64, 256), jnp.bfloat16,
                             sharding=one_chip)
    text = _compiled_text(lambda a, b: correlation_pallas(a, b, 20, 2,
                                                          False),
                          f, f, kernels=["corr_fwd"])
    wide = [ln.strip() for ln in text.splitlines()
            if re.match(r"\s*(ROOT )?%", ln) and "441" in ln
            and "tpu_custom_call" not in ln]
    assert not wide, wide
    assert re.search(r"ROOT %corr_fwd\.\d+ = bf16\[16,48,64,441\]", text)


def test_corr_backward_reads_the_models_layout(one_chip, monkeypatch):
    """The public VJP at the cell's shapes (`ops.corr.correlation`, the
    model's call, its kernels' mode steered to the chip's) compiles to
    `corr_fwd` and `corr_bwd` and nothing between them and the arguments:
    the backward reads the cotangent in the volume's own (B, H, W, 441)
    layout and f1 and f2 as they are, so no transpose, copy or convert of
    a 441-wide array and no pad of f2 stands in the program (until PR 43
    XLA laid the cotangent out as (B, H, 21, 21, W) and padded f2 to
    88 x 112)."""
    from deepof_tpu.ops.corr import correlation

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    f = jax.ShapeDtypeStruct((BATCH, 48, 64, 256), jnp.bfloat16,
                             sharding=one_chip)
    g = jax.ShapeDtypeStruct((BATCH, 48, 64, 441), jnp.bfloat16,
                             sharding=one_chip)

    def fwd_and_vjp(a, b, ct):
        out, vjp = jax.vjp(lambda x, y: correlation(x, y, 20, 2,
                                                    impl="pallas"), a, b)
        return (out, *vjp(ct))

    text = _compiled_text(fwd_and_vjp, f, f, g,
                          kernels=["corr_fwd", "corr_bwd"])
    ops = [ln.strip() for ln in text.splitlines()
           if re.match(r"\s*(ROOT )?%", ln)]
    moved = [ln for ln in ops if re.search(r"441|21,21", ln) and not re.search(
        r"tpu_custom_call| parameter\(| tuple\(| get-tuple-element\(", ln)]
    assert not moved, moved
    assert not [ln for ln in ops if re.search(r" pad\(", ln)]
    assert re.search(r"%corr_bwd\.\d+ = \(bf16\[16,48,64,256\]\S*, "
                     r"bf16\[16,48,64,256\]\S*\) custom-call\(", text)


def _auto_flow_grad(hw):
    """Gradient of the public warp as `impl="auto"` launches it on a TPU:
    two lane tiles (W > 128) go under the per-launch sweep limit."""
    from deepof_tpu.ops.pallas.warp import backward_warp_pallas
    from deepof_tpu.ops.warp import PALLAS_AUTO_MAX_SWEEP

    limit = PALLAS_AUTO_MAX_SWEEP if hw[1] > 128 else None

    def flow_grad(im, fl):
        return jax.grad(lambda x: jnp.sum(backward_warp_pallas(
            im, x, False, sweep_limit=limit) ** 2))(fl)

    return flow_grad


@pytest.mark.parametrize("hw", [(40, 56), (160, 224)])
@pytest.mark.parametrize("n_dev,time", [(1, 1), (4, 1), (4, 2)])
def test_warp_vjp_compiles_through_shard_map(topo, n_dev, time, hw):
    """The public warp (custom_vjp: forward kernel + flow-grad kernel) in
    its one multi-device form — `shard_over_batch` over the mesh the step
    builders publish — on a one-device mesh, on all four described chips
    with the batch sharded over "data", and on a data 2 x time 2 mesh,
    where a data-sharded batch must stay on its "data" shards. At two
    lane tiles each shard decides alone between the kernels and the
    gather (`lax.cond` inside the shard), forward and backward: both
    branches are in the program and no collective carries the decision."""
    from deepof_tpu.parallel.mesh import batch_sharding
    from deepof_tpu.parallel.spatial import mesh_context

    mesh = _mesh(topo, n_dev, time)
    data = batch_sharding(mesh)
    img = jax.ShapeDtypeStruct((BATCH, *hw, 3), jnp.float32, sharding=data)
    flow = jax.ShapeDtypeStruct((BATCH, *hw, 2), jnp.float32, sharding=data)

    with mesh_context(mesh):  # read at trace time, as in train/step.py
        text = _compiled_text(_auto_flow_grad(hw), img, flow,
                              kernels=["warp_fwd", "warp_flow_grad"])
    assert text.count("tpu_custom_call") >= 2  # forward + flow-grad kernels
    assert "all-gather" not in text  # each shard warps its own batch rows
    two_tiles = hw[1] > 128
    assert len(re.findall(r" conditional\(", text)) == (2 if two_tiles else 0)
    # the gather's branch: 12-wide rows of the 2x2 patches
    assert (re.search(r"f32\[\d+,\d+,12\]\S* gather\(", text)
            is not None) == two_tiles


@pytest.mark.parametrize("which", ["fwd", "vjp"])
def test_corr_compiles_through_shard_map_on_four_chips(topo, which):
    """The correlation on the four described chips with the batch sharded
    over "data", alone and with its VJP (both kernels per shard through
    `shard_over_batch`): no shard gathers another's rows."""
    from deepof_tpu.ops.pallas.corr import correlation_pallas
    from deepof_tpu.parallel.mesh import batch_sharding
    from deepof_tpu.parallel.spatial import mesh_context

    mesh = _mesh(topo, 4)
    f = jax.ShapeDtypeStruct((BATCH, 40, 56, 256), jnp.bfloat16,
                             sharding=batch_sharding(mesh))

    def corr(a, b):
        return correlation_pallas(a, b, 20, 2, False)

    def grad(a, b):
        return jax.grad(lambda x, y: jnp.sum(corr(x, y).astype(
            jnp.float32) ** 2), argnums=(0, 1))(a, b)

    with mesh_context(mesh):
        text = _compiled_text(corr if which == "fwd" else grad, f, f,
                              kernels=["corr_fwd"] if which == "fwd"
                              else ["corr_fwd", "corr_bwd"])
    assert "all-gather" not in text


def _mosaic_payloads(fn, *args):
    return re.findall(r'backend_config\s*=\s*"([^"]*)"',
                      jax.jit(fn).lower(*args).as_text())


@pytest.mark.parametrize("hw", [(40, 56), (160, 224)])
def test_mosaic_payload_does_not_depend_on_who_traced(one_chip, hw):
    """The compile cache's key holds the Mosaic payload, which holds its
    ops' source locations: the same warp (forward and flow-gradient
    kernels, one lane tile and two under the `cond`) traced from two call
    stacks must lower to the same bytes (`one_frame_locations`; PR 23 read
    two keys on the chip before it)."""
    img = jax.ShapeDtypeStruct((BATCH, *hw, 3), jnp.float32,
                               sharding=one_chip)
    flow = jax.ShapeDtypeStruct((BATCH, *hw, 2), jnp.float32,
                                sharding=one_chip)
    flow_grad = _auto_flow_grad(hw)

    def from_deeper(im, fl):
        def one_more_frame(im, fl):
            return flow_grad(im, fl)
        return one_more_frame(im, fl)

    direct = _mosaic_payloads(flow_grad, img, flow)
    assert len(direct) == 2 and direct == _mosaic_payloads(from_deeper, img, flow)


@pytest.mark.parametrize("level", [0, 3])
def test_kernel_name_wins_over_the_scope_it_is_called_in(one_chip, level):
    """Inside a `jax.named_scope` (every loss level is one) the custom
    call still takes the kernel's `name=`, not the scope's: without
    `name=` the chip's event was `%name.N` or `%loss_level_<k>.N`, which
    no reduction can tell apart."""
    from deepof_tpu.ops.pallas.warp import _pallas_warp_fwd

    img = jax.ShapeDtypeStruct((BATCH, 40, 56, 3), jnp.float32,
                               sharding=one_chip)
    flow = jax.ShapeDtypeStruct((BATCH, 40, 56, 2), jnp.float32,
                                sharding=one_chip)

    def in_scope(im, fl):
        with jax.named_scope(f"loss_level_{level}"), jax.named_scope("warp"):
            return _pallas_warp_fwd(im, fl, False)

    text = _compiled_text(in_scope, img, flow, kernels=["warp_fwd"])
    assert f"loss_level_{level}/warp" in text  # the scope is in op_name


@pytest.mark.slow
@pytest.mark.parametrize("model,n_dev", [("inception_v3", 1), ("flownet_c", 4)])
def test_whole_train_step_compiles_for_v5e(topo, monkeypatch, model, n_dev):
    """The headline train step (batch 16, 320x448, bf16, warp_impl=auto) as
    `chip_smoke.py` runs it, for one described chip (Inception-v3) and for
    the four-chip `data` mesh (FlowNet-C: both warp kernels and the
    correlation in one program). Minutes, hence slow; the `auto` gates ask
    `jax.default_backend()` and are steered here, never through an option."""
    import dataclasses

    from deepof_tpu.core.config import get_config
    from deepof_tpu.train.warmup import lower_train_step

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get_config("flyingchairs")
    cfg = cfg.replace(
        model=model,
        data=dataclasses.replace(cfg.data, dataset="synthetic",
                                 image_size=(320, 448), gt_size=(320, 448),
                                 batch_size=BATCH),
        train=dataclasses.replace(cfg.train, compute_dtype="bfloat16"))
    compiled = lower_train_step(cfg, _mesh(topo, n_dev)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 12  # every level, the finest too
    assert "%warp_fwd." in text and "%warp_flow_grad." in text
    # kernels or gather, decided inside the shard where there is a mesh
    assert re.search(r"jvp\(loss_level_0\)/warp/(shard_map/)?cond", text)
    assert ("%corr_fwd." in text) == (model == "flownet_c")
    assert "%name." not in text  # what an unnamed Mosaic call was called
    for scope in ("jvp(forward)", "transpose(jvp(forward))", "optimizer",
                  "jvp(loss_level_0)/warp", "transpose(jvp(loss_level_5))"):
        assert f"jit(step)/{scope}/" in text, scope
    assert ("all-reduce" in text) == (n_dev > 1)
    assert "all-gather" not in text
    ma = compiled.memory_analysis()
    assert (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.generated_code_size_in_bytes) < 16e9  # fits one v5e's HBM


KANANA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                      "benchmark", "configs", "kanana2_30b_a3b_ep8.json")


@pytest.mark.parametrize("m", [8192 * 6, 12288])
def test_grouped_expert_product_compiles_for_v5e(one_chip, m):
    """The expert layer's one grouped product a projection at the cell's
    size (`lax.ragged_dot`: 8192 tokens x 6 slots sorted by expert, the 16
    held experts of width 768) at both widths of the sorted row list (all
    49,152 slots; `expert_row_cap`'s 12,288), forward and both gradients:
    XLA's own Mosaic fusion stands where a kernel of the repo's would."""
    k, n, g = 2048, 768, 16
    x = jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((g, k, n), jnp.bfloat16, sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((g,), jnp.int32, sharding=one_chip)

    def loss(x, w, sizes):
        return jnp.sum(jax.lax.ragged_dot(
            x, w, sizes, preferred_element_type=jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        x, w, sizes).compile().as_text()
    assert "tpu_custom_call" in text and "ragged-dot" in text


def _attention_operands(sharding, rows=2, positions=4096, heads=32):
    """The latent attention's five operands at the cell's layer: 2 rows of
    4096 positions, 32 heads of 128 + 64 / 128, bfloat16."""
    def of(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=sharding)

    return (of(rows, positions, heads, 128), of(rows, positions, heads, 64),
            of(rows, positions, heads, 128), of(rows, positions, 64),
            of(rows, positions, heads, 128))


@pytest.mark.parametrize("block_kv", [512, 1024, 2048])
@pytest.mark.parametrize("which", ["fwd", "vjp"])
def test_attention_kernels_compile_for_v5e(one_chip, which, block_kv):
    """The fused causal attention at the cell's layer and `lm.attn_block_q`
    512, at each key block the probe sweeps (2048 is the route's): the
    forward kernel alone, and the custom VJP's pair under a gradient with
    respect to all five operands."""
    from deepof_tpu.ops.pallas.attention import fused_causal_attention

    def attend(*o):
        with jax.named_scope("mla_scores"):  # as the layer calls it
            return fused_causal_attention(*o, 192 ** -0.5, 512, block_kv)

    if which == "fwd":
        _compiled_text(attend, *_attention_operands(one_chip),
                       kernels=["mla_attn_fwd"])
    else:
        _compiled_text(jax.grad(lambda *o: jnp.sum(attend(*o).astype(
            jnp.float32) ** 2), argnums=range(5)),
            *_attention_operands(one_chip),
            kernels=["mla_attn_fwd", "mla_attn_bwd"])


@pytest.mark.parametrize("which", ["fwd", "vjp"])
def test_block_mask_attention_kernels_compile_for_v5e(one_chip, which):
    """The fused grouped-query attention under the block-diffusion mask at
    the second language-model cell's layer (one doubled row of 8192, 32
    query heads over 4 key/value heads of 128, blocks of 4, query tiles of
    512, key tiles of 2048: the route's): the forward kernel alone, and the
    custom VJP's pair under a gradient with respect to q, k and v. The
    rule's position arithmetic (shifts, comparisons, logical operations on
    masks; no vector divide, no select between masks) is what Mosaic has
    to take, and a noised query tile's own chunk in four diagonal
    sub-blocks of 128 (`Mask.band`) inside the same two kernels."""
    _compile_block_mask_attention(one_chip, which, 4, 512)


@pytest.mark.parametrize("which", ["fwd", "vjp"])
@pytest.mark.parametrize("groups,block_q", [(2, 512), (4, 256)],
                         ids=["nemotron_g2", "q256"])
def test_block_mask_attention_kernels_compile_at_other_shapes_for_v5e(
        one_chip, which, groups, block_q):
    """As above over the third language-model cell's 2 key/value heads,
    and with query tiles of 256: two of them a chunk of 512 keys, the
    band's keys at a query tile's offset in its chunk (a traced, aligned
    sublane offset into the key tile)."""
    _compile_block_mask_attention(one_chip, which, groups, block_q)


def _compile_block_mask_attention(one_chip, which, groups, block_q):
    from deepof_tpu.ops.attention import Mask
    from deepof_tpu.ops.pallas.attention import fused_grouped_attention

    mask = Mask("block_diffusion", 4, 4096)

    def of(heads):
        return jax.ShapeDtypeStruct((1, 8192, heads, 128), jnp.bfloat16,
                                    sharding=one_chip)

    def attend(*o):
        with jax.named_scope("gqa_scores"):  # as the layer calls it
            return fused_grouped_attention(*o, 128 ** -0.5, block_q, 2048,
                                           mask)

    if which == "fwd":
        _compiled_text(attend, of(32), of(groups), of(groups),
                       kernels=["bd_attn_fwd"])
    else:
        _compiled_text(jax.grad(lambda *o: jnp.sum(attend(*o).astype(
            jnp.float32) ** 2), argnums=range(3)), of(32), of(groups),
            of(groups), kernels=["bd_attn_fwd", "bd_attn_bwd"])


@pytest.mark.parametrize("which", ["fwd", "vjp"])
def test_window_attention_kernels_compile_for_v5e(one_chip, which):
    """The same kernel bodies under the sliding window at the window cell's
    layer (one row of 16384, 32 query heads over 4 key/value heads of 128,
    W = 2048, query tiles of 512, key tiles of 2048), on their own grids:
    the key tiles and query tiles a window reaches, counted from a tile's
    first, whose index arithmetic (a floor of the window's first key, a
    clamp to the last tile) runs on the scalar core."""
    from deepof_tpu.ops.attention import Mask
    from deepof_tpu.ops.pallas.attention import fused_grouped_attention

    mask = Mask("window", window=2048)

    def of(heads):
        return jax.ShapeDtypeStruct((1, 16384, heads, 128), jnp.bfloat16,
                                    sharding=one_chip)

    def attend(*o):
        with jax.named_scope("swa_scores"):  # as the layer calls it
            return fused_grouped_attention(*o, 128 ** -0.5, 512, 2048, mask)

    if which == "fwd":
        _compiled_text(attend, of(32), of(4), of(4), kernels=["swa_attn_fwd"])
    else:
        _compiled_text(jax.grad(lambda *o: jnp.sum(attend(*o).astype(
            jnp.float32) ** 2), argnums=range(3)), of(32), of(4), of(4),
            kernels=["swa_attn_fwd", "swa_attn_bwd"])


PREP = {
    # the product's output [rows, positions, heads * d], heads, interleaved
    # pairs, normed: what the two language-model cells' layers hand the pass
    "kanana_q_rope_32x64": ((2, 4096, 32 * 64), 32, True, False),
    "kanana_k_rope_1x64": ((2, 4096, 64), 1, True, False),
    "sdar_q_32x128": ((1, 8192, 32 * 128), 32, False, True),
    "sdar_k_4x128": ((1, 8192, 4 * 128), 4, False, True),
}


@pytest.mark.parametrize("case", sorted(PREP))
@pytest.mark.parametrize("which", ["fwd", "vjp"])
def test_qk_prep_kernels_compile_for_v5e(one_chip, which, case):
    """The fused preparation pass between a projection and the attention
    kernels at both cells' shapes and the route's block of 512 positions:
    the latent layer's rotary parts (interleaved pairs; 32 heads of 64, two
    a register, and the one shared key head, a block 64 lanes wide), the
    grouped layer's q and k (per-head norm, rotary halves at the positions
    of a doubled row); the forward kernel alone, and the custom VJP's pair
    under a gradient with respect to the product's output and the scale.
    Lane rolls by 1, 32, 64 and 127, the split of a register into two
    heads, and a lane reduction a head are what Mosaic has to take."""
    from deepof_tpu.ops.attention import CAUSAL, Mask
    from deepof_tpu.ops.pallas.qk_prep import qk_prep

    shape, heads, interleave, normed = PREP[case]
    mask = CAUSAL if interleave else Mask("block_diffusion", 4, shape[1] // 2)
    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    scale = jax.ShapeDtypeStruct((shape[2] // heads,), jnp.float32,
                                 sharding=one_chip)

    def prep(x, scale):
        with jax.named_scope("mla_proj" if interleave else "gqa_proj"):
            return qk_prep(x, mask.rope_positions(shape[1]), heads, 1e6,
                           interleave, jnp.bfloat16, 512,
                           scale if normed else None,
                           1e-6 if normed else None)

    if which == "fwd":
        text = _compiled_text(prep, x, scale, kernels=["qk_prep_fwd"])
        assert f"bf16[{shape[0]},{heads},{shape[1]},{shape[2] // heads}]" in text
    else:
        _compiled_text(jax.grad(lambda x, g: jnp.sum(prep(x, g).astype(
            jnp.float32) ** 2), argnums=(0, 1) if normed else 0), x, scale,
            kernels=["qk_prep_fwd", "qk_prep_bwd"])


def test_attention_compiles_through_shard_map_on_four_chips(topo):
    """Under a mesh the kernels run per batch shard: 4 rows over the four
    described chips' "data" axis, no row gathered."""
    from deepof_tpu.ops.pallas.attention import fused_causal_attention
    from deepof_tpu.parallel.mesh import batch_sharding
    from deepof_tpu.parallel.spatial import mesh_context

    def attend(*o):
        with jax.named_scope("mla_scores"):
            return fused_causal_attention(*o, 192 ** -0.5, 512, 2048)

    mesh = _mesh(topo, 4)
    with mesh_context(mesh):
        text = _compiled_text(
            jax.grad(lambda *o: jnp.sum(attend(*o).astype(jnp.float32) ** 2),
                     argnums=range(5)),
            *_attention_operands(batch_sharding(mesh), rows=4, positions=2048,
                                 heads=4),
            kernels=["mla_attn_fwd", "mla_attn_bwd"])
    assert "all-gather" not in text


@pytest.mark.slow
def test_expert_block_compiles_for_v5e(one_chip, monkeypatch):
    """One expert block of `kanana2_30b_a3b_ep8` (latent attention on the
    chip's route: the fused kernels; router, sort, gather, the grouped
    products, scatter) at the cell's size, 2 rows of 4096 positions in
    bfloat16, forward and backward with its recomputation. Four minutes
    under this suite's `--xla_force_host_platform_device_count=8` (which
    slows the TPU compiler four times: the whole step below takes 51 s in
    a plain process), hence slow."""
    from deepof_tpu.core.config import LMConfig, fill_lm_from_file
    from deepof_tpu.models.lm.model import Block

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    lm = fill_lm_from_file(LMConfig(), KANANA)
    block = Block(lm, True, jnp.bfloat16)
    x = jax.ShapeDtypeStruct((2, 4096, lm.hidden_size), jnp.float32,
                             sharding=one_chip)
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: block.init(jax.random.PRNGKey(0), jnp.zeros(
            (2, 4096, lm.hidden_size)))["params"]))

    def loss(p, x):
        y, counters = jax.checkpoint(
            lambda pp, xx: block.apply({"params": pp}, xx))(p, x)
        return jnp.sum(y * y) + counters["moe_slots_held_share"]

    compiled = jax.jit(jax.grad(loss)).lower(params, x).compile()
    text = compiled.as_text()
    assert "ragged-dot" in text and "moe_dispatch" in text
    assert " conditional(" in text  # the compact row list and the full width
    assert "%mla_attn_fwd." in text and "%mla_attn_bwd." in text
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes < 8e9, ma


@pytest.mark.slow
def test_language_model_step_fits_one_v5e(topo, monkeypatch):
    """The whole train step of `kanana2_30b_a3b_ep8.train_4k` (the
    Trainer's own step through `lower_train_step`: 5 layers at the published
    widths, 2 rows of 4096 positions, bfloat16, per-layer recomputation,
    Adam) compiles for one described v5e, and arguments + temporaries +
    code stay under the chip's 16.9 GB (`bytes_limit` 16,909,336,064). The
    attention's route asks `jax.default_backend()` and is steered to the
    chip's here, never through an option: ten Mosaic calls under
    `mla/mla_scores` (a forward and a backward kernel a layer: the
    recomputed layer keeps the forward's output and logsumexp) and no
    block of float32 scores anywhere."""
    from deepof_tpu import cli
    from deepof_tpu.train.warmup import lower_train_step

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = cli.config_for([
        "train", "--preset", "lm", "--set", f"lm.config_file={KANANA}",
        "--set", "lm.seq_len=4096", "--set", "data.batch_size=2",
        "--set", "train.compute_dtype=bfloat16", "--set", "train.remat=true",
        "--set", "lm.attn_block_q=512", "--set", "lm.loss_block=2048"])
    compiled = lower_train_step(cfg, _mesh(topo, 1)).compile()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
             + ma.generated_code_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes)
    assert total < 16_909_336_064, ma
    # 4.99 GB with the expert layer's two widths (3.98 before them); 7.14
    # where the optimizer's first use of the experts' gradients is sunk
    # into the backward `cond`'s branches, 10.41 where the `cond` is
    # differentiated as it stands (`layers.py::add_routed_within`)
    assert ma.temp_size_in_bytes < 6e9, ma
    assert ma.argument_size_in_bytes == pytest.approx(12 * 575955968, rel=1e-3)
    text = compiled.as_text()
    assert "ragged-dot" in text
    # the expert layer's sorted row list at both widths, each under its
    # own scopes inside the `cond` (branch 1, the predicate true: compact)
    for scope in ("mla/mla_scores", "moe/moe_dispatch",
                  "moe/cond/branch_1_fun/moe_experts",
                  "moe/cond/branch_0_fun/moe_experts",
                  "moe/cond/branch_1_fun/transpose(jvp(moe_combine))",
                  "lm_head", "loss_ce", "optimizer"):
        assert scope in text, scope
    assert "bf16[12288,2048]" in text and "bf16[49152,2048]" in text
    kernels = [ln for ln in text.splitlines()
               if "tpu_custom_call" in ln and "mla/mla_scores/mla_attn" in ln]
    assert sum("%mla_attn_fwd." in ln and "jvp(LatentMoELM)/layer_" in ln
               and "transpose(" not in ln for ln in kernels) == 5, kernels
    assert sum("%mla_attn_bwd." in ln and "transpose(jvp(" in ln
               for ln in kernels) == 5, kernels
    assert len(kernels) == 10  # the layer's recomputation reran no forward
    assert "f32[2,32,512," not in text  # a block of scores in HBM


@pytest.mark.parametrize("which", ["fwd", "vjp"])
def test_state_space_scan_kernels_compile_for_v5e(one_chip, which):
    """The state-space scan's kernels at the third language-model cell's
    layer (one row of 4096 positions a copy, 64 heads of 64 in 8 groups, a
    state of 128, chunks of 128 holding blocks of 4, bfloat16 products on
    the float32 activations the layer hands over): the forward kernel
    alone, and the custom VJP's pair under a gradient with respect to all
    nine inputs, each called inside the layer's scope."""
    from deepof_tpu.ops.pallas.ssd import doubled_scan

    def of(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    half = (of(1, 4096, 64, 64), of(1, 4096, 64), of(1, 4096, 8, 128),
            of(1, 4096, 8, 128))
    args = (*half, *half, of(64))

    def scan(*a):
        with jax.named_scope("mamba_scan"):  # as the layer calls it
            return doubled_scan(*a, 128, 4, jnp.bfloat16)

    if which == "fwd":
        text = _compiled_text(scan, *args, kernels=["ssd_fwd"])
    else:
        text = _compiled_text(jax.grad(lambda *a: sum(
            jnp.sum(y * y) for y in scan(*a)), argnums=range(9)), *args,
            kernels=["ssd_fwd", "ssd_bwd"])
    assert "mamba_scan" in text  # the scope is in op_name
