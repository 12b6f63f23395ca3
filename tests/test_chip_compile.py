"""Ask the chip's compiler, without the chip.

The TPU compiler is installed here and compiles for a *described* v5e
device (``/opt/skills/guides/on-chip-measurement`` section 2). Interpret
mode — what every other test runs the Pallas kernels in — cannot see a
block Mosaic refuses or a program that does not fit; these compiles can.
Nothing runs, so nothing here says anything about results or times.

All of it lives in this ONE file: the worker that is given it loads the
TPU library inside the ``topo`` fixture, and no other worker may.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from fedrec_tpu.config import ExperimentConfig
from fedrec_tpu.fed import get_strategy
from fedrec_tpu.models import NewsRecommender
from fedrec_tpu.ops import attention_kernels, fused_hot_path
from fedrec_tpu.train import build_fed_train_step, build_param_sync
from fedrec_tpu.train.state import init_client_state, replicate_state
from fedrec_tpu.train.step import NEWS_INVERSE, NEWS_ROWS, encode_rows_for

# the flagship's published widths (ModelConfig defaults)
B, HIS, HEADS, HEAD_DIM = 64, 50, 20, 20
TITLE, TRUNK, QUERY, NEWS_DIM = 50, 768, 200, 400
CANDS = 5
UNIQUE_CAP = B * (CANDS + HIS)      # 3520: what the joint step gathers per client
TABLE_ROWS = 65_536                  # MIND-small
STEP_TABLE_ROWS = 4_096              # the step cases: small enough to compile fast


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler on this rig
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device can be written to the persistent
    # cache but never read back; keep it off around these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def compiled_kernels(monkeypatch):
    """On a TPU backend a kernel compiles or the call raises; here the
    backend is the CPU, so the test makes the kernels' choice for them."""
    monkeypatch.setattr(attention_kernels, "_interpret", lambda: False)
    monkeypatch.setattr(fused_hot_path, "_interpret", lambda: False)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _compile(fn, *specs):
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# ---------------------------------------------------------------- kernels
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("his_len", [HIS, 1024])
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash_attention_compiles(compiled_kernels, one_chip, direction, his_len, dtype):
    qkv = _spec((B, his_len, HEADS, HEAD_DIM), dtype, one_chip)
    mask = _spec((B, his_len), "float32", one_chip)

    def fwd(q, k, v, m):
        return attention_kernels.flash_attention(q, k, v, m)

    def bwd(q, k, v, m):
        return jax.grad(
            lambda q, k, v: fwd(q, k, v, m).astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)

    _compile(fwd if direction == "fwd" else bwd, qkv, qkv, qkv, mask)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_additive_pool_compiles(compiled_kernels, one_chip, dtype):
    """The text head's pool over the rows one client's step gathers."""
    _compile(
        attention_kernels.additive_pool,
        _spec((UNIQUE_CAP, TITLE, TRUNK), dtype, one_chip),
        _spec((TRUNK, QUERY), dtype, one_chip),
        _spec((QUERY,), dtype, one_chip),
        _spec((QUERY,), dtype, one_chip),
    )


def _text_head_specs(dtype, sharding):
    return {
        "pool": {
            "att_fc1": {"kernel": _spec((TRUNK, QUERY), dtype, sharding),
                        "bias": _spec((QUERY,), dtype, sharding)},
            "att_fc2": {"kernel": _spec((QUERY, 1), dtype, sharding),
                        "bias": _spec((1,), dtype, sharding)},
        },
        "fc": {"kernel": _spec((TRUNK, NEWS_DIM), dtype, sharding),
               "bias": _spec((NEWS_DIM,), dtype, sharding)},
    }


def _user_tower_specs(dtype, sharding):
    proj = HEADS * HEAD_DIM
    dense = lambda i, o: {"kernel": _spec((i, o), dtype, sharding),  # noqa: E731
                          "bias": _spec((o,), dtype, sharding)}
    attn = {"w_q": dense(NEWS_DIM, proj), "w_k": dense(NEWS_DIM, proj),
            "w_v": dense(NEWS_DIM, proj)}
    pool = {"att_fc1": dense(proj, QUERY), "att_fc2": dense(QUERY, 1)}
    return attn, pool


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_fused_gather_encode_compiles(compiled_kernels, one_chip, direction):
    """The open risk of fused_hot_path.py's header: the (1, 50, Dh) table
    block over the real MIND-small table."""
    table = _spec((TABLE_ROWS, TITLE, TRUNK), "bfloat16", one_chip)
    uniq = _spec((UNIQUE_CAP,), "int32", one_chip)
    params = _text_head_specs("float32", one_chip)

    def fwd(table, uniq, params):
        return fused_hot_path.fused_gather_encode(table, uniq, params)

    def bwd(table, uniq, params):
        return jax.grad(
            lambda p: fwd(table, uniq, p).astype(jnp.float32).sum()
        )(params)

    _compile(fwd if direction == "fwd" else bwd, table, uniq, params)


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_fused_history_score_compiles(compiled_kernels, one_chip, direction):
    """The forward (what serving's ``fused_user_vector`` runs) compiles. The
    backward is REFUSED by the chip's compiler: its kernel is built from
    einsums with no MXU form (outer products, two contracted axes), which is
    a rewrite and not a local repair, so ``model.fuse_hot_path=true`` cannot
    train on the chip and stays off by default. Roadmap S5 decides its
    fate; when the kernel is rewritten or deleted this assertion goes."""
    his = _spec((B, HIS, NEWS_DIM), "bfloat16", one_chip)
    cand = _spec((B, CANDS, NEWS_DIM), "bfloat16", one_chip)
    mask = _spec((B, HIS), "float32", one_chip)
    attn, pool = _user_tower_specs("float32", one_chip)

    def fwd(his, cand, mask, attn, pool):
        return fused_hot_path.fused_history_score(
            his, cand, mask, attn, pool, HEADS
        )[0]

    def bwd(his, cand, mask, attn, pool):
        return jax.grad(
            lambda a, p: fwd(his, cand, mask, a, p).astype(jnp.float32).sum(),
            argnums=(0, 1),
        )(attn, pool)

    if direction == "fwd":
        _compile(fwd, his, cand, mask, attn, pool)
        return
    with pytest.raises(Exception, match="TPU_DotDimensionNumbersAttr"):
        _compile(bwd, his, cand, mask, attn, pool)


# ------------------------------------------------------------ whole step
def _joint_step_case(devices, num_clients, batch=B, strategy="param_avg",
                     rows=STEP_TABLE_ROWS, encode_rows=0):
    """(step, sync, args): the default (XLA) joint train step and the
    round-end sync over a mesh of described devices, full width, bf16.
    ``encode_rows``: the batch carries the host's dedup at that size."""
    cfg = ExperimentConfig()
    cfg.model.text_encoder_mode = "head"
    cfg.model.dtype = "bfloat16"
    cfg.fed.strategy = strategy
    cfg.fed.num_clients = num_clients
    cfg.data.batch_size = batch
    model = NewsRecommender(cfg.model)
    axis = cfg.fed.mesh_axis
    mesh = Mesh(np.array(devices), (axis,))
    per_client = NamedSharding(mesh, P(axis))
    state = jax.eval_shape(
        lambda: replicate_state(
            init_client_state(
                model, cfg, jax.random.PRNGKey(0), rows, TITLE
            ),
            num_clients, jax.random.PRNGKey(1),
        )
    )
    state = jax.tree_util.tree_map(
        lambda x: _spec(x.shape, x.dtype, per_client), state
    )
    slots = batch * (CANDS + HIS)
    batch = {
        "candidates": _spec((num_clients, batch, CANDS), "int32", per_client),
        "history": _spec((num_clients, batch, HIS), "int32", per_client),
        "labels": _spec((num_clients, batch), "int32", per_client),
    }
    if encode_rows:
        batch[NEWS_ROWS] = _spec((num_clients, encode_rows), "int32", per_client)
        batch[NEWS_INVERSE] = _spec((num_clients, slots), "int32", per_client)
    table = _spec((rows, TITLE, TRUNK), "bfloat16", NamedSharding(mesh, P()))
    weights = _spec((num_clients,), "float32", NamedSharding(mesh, P()))
    step = build_fed_train_step(
        model, cfg, get_strategy(strategy), mesh, mode="joint"
    )
    sync = build_param_sync(cfg, mesh)
    return step, sync, (state, batch, table), weights


def _table_copies(compiled, table) -> list[str]:
    """``copy`` instructions whose result has the whole table's shape, as
    ``chip_smoke.py`` looks for them in the step that ran on the chip."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    import chip_smoke

    return chip_smoke.table_copies(compiled.as_text(), table)


def _table_layout(compiled) -> tuple:
    return tuple(compiled.input_formats[0][2].layout.major_to_minor)


def test_joint_step_compiles_for_one_chip(topo):
    """8-client cohort, B=64 per client, on one described chip."""
    step, _, args, _ = _joint_step_case(topo.devices[:1], num_clients=2)
    compiled = step.lower(*args).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < total < 16e9
    assert "all-reduce" not in compiled.as_text()  # param_avg: no per-step sync
    # the table is read where it rests: row-major, stated by the program
    assert _table_layout(compiled) == (0, 1, 2)
    assert _table_copies(compiled, args[2]) == []


def test_the_chips_own_table_layout_costs_the_step_a_copy(topo):
    """Why the step states the table's format (PERF.md section 5): the same
    program left to the chip's own layout for (N, 50, 768) takes the table
    with the 50-axis major and rewrites all of it before the gather."""
    step, _, args, _ = _joint_step_case(topo.devices[:1], num_clients=2)
    compiled = jax.jit(step.__wrapped__).lower(*args).compile()
    assert _table_layout(compiled) == (1, 0, 2)
    assert len(_table_copies(compiled, args[2])) == 1


def test_single_worker_step_lays_the_gathered_rows_out_once(topo):
    """``central.b512``'s step: one client, B=512, un-vmapped. Its text head
    is compiled as a batch of one (``_encode_gathered``): un-batched,
    XLA:TPU copies the 28,160 gathered rows into another layout for the
    head's first product (7.03 ms a step; ledger, PR 26) and the step's
    temporaries reach 6.28 GB, which does not fit beside the benchmark's
    two resident tables (PERF.md section 6, PR 27)."""
    import re

    step, _, args, _ = _joint_step_case(
        topo.devices[:1], num_clients=1, batch=512, strategy="grad_avg",
        rows=32_768,    # more rows than the step gathers, as in the cell
    )
    compiled = step.lower(*args).compile()
    gathered = re.escape(f"bf16[{512 * (CANDS + HIS)},{TITLE},{TRUNK}]")
    # (inside a fusion such a copy is a ROOT and costs no buffer of its own)
    assert re.findall(rf"\n  %\S+ = {gathered}\S* copy\(", compiled.as_text()) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 4.5e9
    assert _table_copies(compiled, args[2]) == []


def _gathered_rows_copies(compiled, clients, rows) -> list[str]:
    """Top-level ``copy`` instructions of the gathered rows' size (inside a
    fusion such a copy is a ROOT and costs no buffer of its own)."""
    import re

    shapes = "|".join(re.escape(s) for s in (
        f"bf16[{clients * rows},{TITLE},{TRUNK}]",
        f"bf16[{clients},{rows},{TITLE},{TRUNK}]",
    ))
    return re.findall(rf"\n  %\S+ = (?:{shapes})\S* copy\(", compiled.as_text())


# the largest distinct count of a round's first 32 steps under ``rounds32``
# (PERF.md section 6, PR 30), and the size the round loop derives from it
HEAD_CELLS = {
    "fed8.b64": dict(num_clients=8, batch=64, strategy="param_avg", most=2_738),
    "central.b512": dict(num_clients=1, batch=512, strategy="grad_avg", most=13_970),
}


@pytest.mark.parametrize("cell", sorted(HEAD_CELLS))
def test_host_deduped_step_gathers_and_encodes_r_rows(topo, cell):
    """The head-mode cells' step fed the host's dedup at the size the round
    loop chooses: the dedup's sort is gone from the program, the gather
    brings R rows a client and nothing of the slot count, and the rows are
    laid out once."""
    import re

    case = dict(HEAD_CELLS[cell])
    slots = case["batch"] * (CANDS + HIS)
    rows = encode_rows_for(case.pop("most"), slots)
    assert rows == {"fed8.b64": 2_880, "central.b512": 14_400}[cell]
    step, _, args, _ = _joint_step_case(
        topo.devices[:1], rows=32_768, encode_rows=rows, **case
    )
    compiled = step.lower(*args).compile()
    text = compiled.as_text()
    k = case["num_clients"]
    assert f"bf16[{k * rows},{TITLE},{TRUNK}]" in text
    assert f"bf16[{k * slots},{TITLE},{TRUNK}]" not in text
    # the one sort left orders the inverse for the scatter-add of the news
    # vectors' cotangents (the transpose of ``vecs[inv]``), as at the parent
    sorts = re.findall(r" sort\(", text)
    assert len(sorts) == 1 and f"s32[{k * slots}]" in text
    assert _gathered_rows_copies(compiled, k, rows) == []
    assert _table_copies(compiled, args[2]) == []
    assert _table_layout(compiled) == (0, 1, 2)


def test_a_whole_number_of_row_tiles_costs_the_cohort_a_copy(topo):
    """Why R is 64 past a multiple of 128 (``train/step.py:
    ENCODE_ROW_RESIDUE``), as the slot count 3,520 is: at 2,944 = 23 x 128
    rows XLA:TPU lays the cohort's gathered rows out a second time, R minor,
    for the head's weight gradient: 1.8 GB more of temporaries a step."""
    case = dict(HEAD_CELLS["fed8.b64"])
    case.pop("most")
    step, _, args, _ = _joint_step_case(
        topo.devices[:1], rows=32_768, encode_rows=2_944, **case
    )
    compiled = step.lower(*args).compile()
    assert len(_gathered_rows_copies(compiled, 8, 2_944)) == 1


def test_joint_step_compiles_for_four_chips(topo):
    """One client per device over the ``clients`` mesh: the step's only
    collective is the psum behind its mean-loss metric, and the round-end
    sync averages the parameters with an all-reduce over the four chips."""
    step, sync, args, weights = _joint_step_case(topo.devices[:4], num_clients=4)
    compiled = step.lower(*args).compile()
    step_text = compiled.as_text()
    assert "replica_groups={{0,1,2,3}}" in step_text
    assert _table_layout(compiled) == (0, 1, 2)
    assert _table_copies(compiled, args[2]) == []
    sync_text = sync.lower(args[0], weights).compile().as_text()
    assert "all-reduce" in sync_text and "replica_groups={{0,1,2,3}}" in sync_text


# ------------------------------------------ the sparse-expert trunk's layer
# one chunk of the expert layer in the three routed cells: tokens, choices a
# token, experts held and in all, hidden and expert widths, the activation
ROUTED_CHUNKS = {
    "st21b-ep4.b16": (11_000, 6, 16, 64, 2560, 768, jax.nn.relu),
    "xing29b-ep8.b2": (5_500, 4, 8, 64, 3584, 1024, jax.nn.silu),
    "laguna33b-ep8.b1": (11_264, 8, 32, 256, 2048, 512, jax.nn.silu),
}
# the sorted buffer's small and full size in each
BUFFER_ROWS = {
    "st21b-ep4.b16": (33_280, 66_048), "xing29b-ep8.b2": (5_632, 22_016),
    "laguna33b-ep8.b1": (22_528, 90_112),
}


def _held_experts_chunk(one_chip, cell, clients=1):
    """The chunk's loss and gradient (forward and backward), compiled for
    one chip."""
    from fedrec_tpu.models import sparse_trunk

    tokens, k, held, experts, d, f, activation = ROUTED_CHUNKS[cell]
    lead = () if clients == 1 else (clients,)
    args = (
        _spec(lead + (tokens, d), "bfloat16", one_chip),
        _spec(lead + (tokens, k), "int32", one_chip),
        _spec(lead + (tokens, k), "float32", one_chip),
        _spec(lead + (held, d, f), "bfloat16", one_chip),
        _spec(lead + (held, d, f), "bfloat16", one_chip),
        _spec(lead + (held, f, d), "bfloat16", one_chip),
    )

    def loss(u, idx, p, w_gate, w_up, w_down):
        y, sizes, full_size = sparse_trunk.held_experts_output(
            u, idx, p, w_gate, w_up, w_down, 0, experts, activation)
        return jnp.sum(y.astype(jnp.float32)), (sizes, full_size)

    def both(*a):
        grad = jax.value_and_grad(loss, argnums=(0, 3, 4, 5), has_aux=True)
        return (jax.vmap(grad) if clients > 1 else grad)(*a)

    return _compile(both, *args)


def _batched_products(text: str) -> str:
    return "".join(
        line for line in text.splitlines()
        if "ragged-dot(" in line and "lhs_batch_dims={0}" in line
    )


@pytest.mark.parametrize("clients", [1, 2], ids=["one-client", "cohort-of-two"])
def test_held_experts_compile_at_published_widths(topo, one_chip, clients):
    """One chunk of the expert layer of ``st21b-ep4.b16`` (11,000 tokens, 6
    choices, 16 of 64 experts of 2560 x 768 held), forward and backward: the
    grouped products reach XLA:TPU's grouped-matmul kernel un-batched. It
    refuses them a batch dimension, so under a cohort's ``vmap`` they run
    client by client (``sparse_trunk.grouped_matmul``), at both sizes of
    the sorted buffer (the choice between them is a select there)."""
    text = _held_experts_chunk(one_chip, "st21b-ep4.b16", clients).as_text()
    # three products forward, and for each its two transposes
    assert text.count('custom_call_target="tpu_custom_call"') >= 9
    assert _batched_products(text) == ""


@pytest.mark.parametrize("cell", sorted(ROUTED_CHUNKS))
def test_held_experts_choose_between_two_buffer_sizes(topo, one_chip, monkeypatch, cell):
    """The chunk of each routed cell compiles with a conditional between the
    sorted buffer's small size and its full one (``BUFFER_ROWS``), forward
    and backward, the grouped products un-batched in both branches; the
    full-size branch is the larger, so the program needs no more temporary
    memory than with one size, the full one."""
    from fedrec_tpu.models import sparse_trunk

    tokens, k, held, experts, *_ = ROUTED_CHUNKS[cell]
    small, full = sparse_trunk.buffer_rows(tokens * k, held, experts)
    assert (small, full) == BUFFER_ROWS[cell]
    two_sizes = _held_experts_chunk(one_chip, cell)
    text = two_sizes.as_text()
    assert " conditional(" in text
    assert f"[{small}," in text and f"[{full}," in text
    # a branch: three products forward; in the backward pass the three again
    # and their two transposes each
    assert text.count('custom_call_target="tpu_custom_call"') >= 2 * (3 + 9)
    assert _batched_products(text) == ""
    monkeypatch.setattr(sparse_trunk, "EVEN_SHARE_ROOM", experts // held)
    one_size = _held_experts_chunk(one_chip, cell)
    assert " conditional(" not in one_size.as_text() and f"[{small}," not in one_size.as_text()
    assert (two_sizes.memory_analysis().temp_size_in_bytes
            <= one_size.memory_analysis().temp_size_in_bytes)


@pytest.mark.parametrize("cell", sorted(ROUTED_CHUNKS))
def test_held_experts_lay_no_rows_out_in_pair_order(topo, one_chip, cell):
    """The compiled chunk of each routed cell, forward and backward, holds no
    value of shape ``[T,k,d]`` or ``[T*k,d]``: with 4, 6 or 8 choices as the
    second-minor axis every row tile of 8 (float32) or 16 (bfloat16) is
    padded, and each reshape between the two moves the array (PERF.md
    section 6, PR 36). What runs over a token's choices is k passes over
    ``[T,d]``: the only row counts beside the tokens' are the buffer's two
    sizes (in ``laguna33b-ep8.b1`` the pairs are whole tiles, so the full
    size has ``T*k`` rows itself)."""
    import re

    tokens, k, _, _, d, *_ = ROUTED_CHUNKS[cell]
    text = _held_experts_chunk(one_chip, cell).as_text()
    shapes = {tuple(map(int, dims.split(","))) for dims in re.findall(r"\w+\[([\d,]+)\]", text)}
    assert not {(tokens, k, d), (k, tokens, d)} & shapes
    assert {s[0] for s in shapes if len(s) == 2 and s[1] == d and s[0] >= tokens} == {
        tokens, *BUFFER_ROWS[cell]}


# ------------------------------ the latent trunk's whole step, one chip's share
# what the runtime leaves a program on one v5e chip (``bytes_limit`` of
# ``device.memory_stats()``: PERF.md section 4)
V5E_BYTES_LIMIT = 16.909e9


@pytest.mark.time_limit(900)
def test_latent_trunk_step_fits_one_chip_at_published_widths(topo):
    """``xing29b-ep8.b2``'s train step (Xing4.0-29B-A4B's widths, one dense
    and four routed layers, 8 of 64 experts and an eighth of the vocabulary
    held, B=2: 5,500 tokens, bfloat16 compute) compiles for one described
    v5e chip with its arguments (709M parameters and Adam's two moments) and
    its temporaries (the gradient, the bfloat16 weight copies, one
    sublayer's rematerialised forward) under the chip's limit; the grouped
    products reach the grouped-matmul kernel. The TPU compiler takes two
    minutes over it here."""
    cfg = ExperimentConfig().apply_overrides([
        "fed.num_clients=1", "fed.strategy=grad_avg", "data.batch_size=2",
        "model.text_encoder_mode=finetune", "model.text_trunk=latent_moe",
        "model.bert_hidden=3584", "model.trunk_layers=5", "model.trunk_dense_layers=1",
        "model.trunk_heads=32", "model.trunk_ffn=1024", "model.trunk_vocab=16384",
        "model.trunk_first_expert=0", "model.trunk_experts_held=8",
        "model.dtype=bfloat16", "model.dropout_rate=0.0",
    ])
    model = NewsRecommender(cfg.model)
    mesh = Mesh(np.array(topo.devices[:1]), (cfg.fed.mesh_axis,))
    per_client = NamedSharding(mesh, P(cfg.fed.mesh_axis))
    state = jax.eval_shape(
        lambda: replicate_state(
            init_client_state(model, cfg, jax.random.PRNGKey(0), TABLE_ROWS, TITLE),
            1, jax.random.PRNGKey(1),
        )
    )
    params = sum(
        x.size for x in jax.tree_util.tree_leaves((state.user_params, state.news_params))
    )
    assert params == 709_191_264                 # x 16 B = 11.35 GB with the gradient
    state = jax.tree_util.tree_map(lambda x: _spec(x.shape, x.dtype, per_client), state)
    batch = {
        "candidates": _spec((1, 2, CANDS), "int32", per_client),
        "history": _spec((1, 2, HIS), "int32", per_client),
        "labels": _spec((1, 2), "int32", per_client),
    }
    tokens = _spec((TABLE_ROWS, 2, TITLE), "int32", NamedSharding(mesh, P()))
    step = build_fed_train_step(model, cfg, get_strategy("grad_avg"), mesh, mode="finetune")
    compiled = step.lower(state, batch, tokens).compile()
    mem = compiled.memory_analysis()
    assert 8.5e9 < mem.argument_size_in_bytes < 8.6e9          # 12 B a parameter at rest
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_BYTES_LIMIT
    text = compiled.as_text()
    # 4 routed layers x 3 products x (forward, rematerialised, two transposes)
    assert text.count('custom_call_target="tpu_custom_call"') >= 48


# ---------------------------- the window trunk's attention, one chunk of texts
@pytest.mark.parametrize("kind,heads", [("full", 48), ("window", 64)])
def test_window_trunk_attention_chunk_holds_no_square(topo, one_chip, kind, heads):
    """One chunk (5 texts of 1,024 tokens) of ``laguna33b-ep8.b1``'s attention
    sublayer at published widths (48 / 64 query heads over 8 key/value heads
    of 128, window 512), value and gradient, compiles for one described v5e
    chip: the blocked core's largest scores are one query block of 128
    against its band (at most 640 keys in a window layer, 1,024 in a full
    one), never a text's 1,024 x 1,024 square, and the chunk's temporaries
    stay under 1.2 GB (the cell runs one text a chunk; five is the larger program)."""
    from fedrec_tpu.models import window_trunk

    cfg = window_trunk.WindowTrunkConfig(n_layers=5, experts_held=32, vocab_held=12544)
    assert cfg.heads(kind) == heads
    module = window_trunk._Attention(cfg, kind, jnp.bfloat16)
    x, mask = _spec((5, 1024, 2048), "bfloat16", one_chip), _spec((5, 1024), "int32", one_chip)
    params = jax.eval_shape(lambda x, m: module.init(jax.random.PRNGKey(0), x, m), x, mask)
    params = jax.tree_util.tree_map(lambda a: _spec(a.shape, a.dtype, one_chip), params)

    def loss(p, x, m):
        return jnp.sum(module.apply(p, x, m).astype(jnp.float32))

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(params, x, mask).compile()
    text = compiled.as_text()
    # no L x L array, forward or backward: no array of a head's scores has
    # the text's 1,024 queries beside its 1,024 keys
    import re

    shapes = {tuple(map(int, dims.split(","))) for dims in re.findall(r"(?:f32|bf16)\[([\d,]+)\]", text)}
    assert not any(len(s) >= 4 and s.count(1024) >= 2 for s in shapes)
    band = 640 if kind == "window" else 1024
    assert f"f32[5,8,{heads // 8},128,{band}]" in text or f"f32[5,8,{band},128,{heads // 8}]" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1.2e9
