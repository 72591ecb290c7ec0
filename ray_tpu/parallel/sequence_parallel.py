"""The residual stream sharded over `tp` along the sequence (Megatron's
sequence parallelism), and the two tensor-parallel matmuls that read and
write it.

The parameters carry the Megatron split by rule (parallel/sharding.py: heads,
kv_heads, mlp, vocab -> `tp`). With the stream replicated over `tp` the
partitioner closes every row-parallel matmul (`wo`, `w_down`) with an
all-reduce of the whole (B, S, E) array, whose result the next operation
needs: nothing can run beside it (82.5 ms of a 638 ms step on
train-mistral7b-fsdp2tp2, PERF.md section 6, PR 54). Here, where a step is
traced under a context mesh with `tp` > 1 (`stream_shards`):

- `constrain_stream` holds the stream between sublayers with its sequence
  axis over `tp`: the residual adds, the block norms, the scan's carry and a
  kept `attn_residual` are half the rows a device at `tp` = 2;
- `column_parallel` is gather-then-matmul: the normed stream's pieces go round
  the `tp` ring, each device multiplies the piece it holds by its columns of
  the weights while the next piece is on the link (`wq/wk/wv`, `w_up/w_gate`);
  q, k, v are put in sequence order for the attention kernel by a select that
  fuses into their readers, up and gate stay the pieces they are made as;
- `row_parallel` is matmul-then-scatter: each device multiplies one piece of
  the rows at a time by its rows of the weights and adds it to the partial
  sum that is going round, which ends on the device that owns those rows
  (`wo`, `w_down`): half the bytes of the all-reduce on the link, each
  transfer under the next piece's matmul.

The constraint alone was tried first and is not enough: the partitioner
answers it with all-to-alls and gathered weights and keeps the all-reduces
(PERF.md section 6, PR 54), so the two matmuls are written out in a
`shard_map` with their `ppermute`s. Both sum the same addends as the
all-reduce did. With no context mesh, inside somebody else's `shard_map`
(ring, Ulysses, pipeline, explicit-dp: manual axes), with `tp` = 1 or a
(batch, sequence) that the data axes and `tp` do not divide, all three are
the plain operation: nothing is added to such a program.
"""

from __future__ import annotations

import math
from typing import Any, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from .mesh import DATA_AXES


def stream_shards(mesh: Any, batch: int, seq: int) -> int:
    """The devices that share one sequence of the residual stream between
    sublayers of a (batch, seq) step under `mesh` (a Mesh, or the abstract
    mesh a step is traced under): the mesh's `tp`, or 1 where the stream is
    left as it is (the module's docstring says where)."""
    if mesh.empty or getattr(mesh, "manual_axes", ()):
        return 1
    tp = mesh.shape.get("tp", 1)
    data = math.prod(mesh.shape.get(a, 1) for a in DATA_AXES)
    return tp if tp > 1 and seq % tp == 0 and batch % data == 0 else 1


def _data_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in DATA_AXES if a in mesh.shape)


def constrain_stream(x: jax.Array, whole: bool = False) -> jax.Array:
    """The residual stream x (B, S, E) as it is held between sublayers: batch
    over the data axes and the sequence over `tp`; `whole=True` asks for whole
    sequences (the stack's output for the head; the input of a projection
    that is not written out here). x itself where `stream_shards` is 1."""
    mesh = jax.sharding.get_abstract_mesh()
    if stream_shards(mesh, *x.shape[:2]) == 1:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, PartitionSpec(_data_axes(mesh), None if whole else "tp", None)))


def _specs(equation: str, mesh) -> Tuple[PartitionSpec, PartitionSpec, PartitionSpec]:
    """(activation, weight, result) specs of one tensor-parallel einsum whose
    letters are the models': `b` batch (the data axes), `s` sequence (over
    `tp` in the operand that is the STREAM, the one with `e`), `e` the
    stream's features (whole), and the first of the weight's letters that the
    stream lacks (`h` heads, `f` the MLP's features) over `tp`."""
    operands, result = equation.split("->")
    activation, weight = operands.split(",")
    split = next(letter for letter in weight if letter != "e")

    def spec(letters: str) -> PartitionSpec:
        return PartitionSpec(*(
            _data_axes(mesh) if letter == "b" else
            "tp" if letter == split or (letter == "s" and "e" in letters) else None
            for letter in letters))

    return spec(activation), spec(weight), spec(result)


def _ring(n: int):
    return [(j, (j + 1) % n) for j in range(n)]


def _after_small_weights(piece: jax.Array, weights: Sequence[jax.Array]) -> jax.Array:
    """`piece`, which cannot be handed on before the SMALL ones of `weights`
    (an eighth of its bytes or less) are whole. The compiler starts a ring's
    first transfer as early as it can, and a weight whose `fsdp` gather it
    left synchronous at the top of the scanned block then queues behind 50 MB
    on the same links: 2.2 ms a call for the 4 MB of `wk` where it takes 0.05
    (chip run, PR 54). The scheduler keeps no order but that of the data, so
    the piece is given a dependency on one number of each such weight, in the
    fusion that writes the piece: where their sum is no number the piece is
    none either (as every product with that weight then is), and the piece
    itself everywhere else. A large weight's gather stays where the compiler
    carries it under a matmul."""
    small = [w for w in weights if w.size * w.dtype.itemsize * 8 <= piece.size * piece.dtype.itemsize]
    if not small:
        return piece
    tick = jax.lax.stop_gradient(sum(w.ravel()[0].astype(piece.dtype) for w in small))
    return jnp.where(jnp.isnan(tick), tick, piece)


def column_parallel(h: jax.Array, *matmuls: Tuple[str, jax.Array], ordered: bool = True):
    """[einsum(equation, h, w) for (equation, w) in matmuls], h (B, S, E) the
    normed stream and every w, cast to h's dtype, split over `tp` along its
    output features (`bse,ehd->bhsd`, `bse,ef->bsf`). Where the stream's
    sequence lies over `tp`: ONE gather for all of them, a piece of the
    sequence at a time, each device multiplying the piece it holds while it
    hands it on to its neighbour; the results have whole sequences and their
    features over `tp`. `ordered=False` is for results that only row-wise
    work reads before `row_parallel(..., ordered=False)` takes them: each is
    then the TUPLE of its pieces (B, S / tp, ...) as the ring delivers them,
    piece i the rows of the device i hops up the ring (a tuple of one, the
    plain einsum, where there is no ring), and nothing is brought into place."""
    mesh = jax.sharding.get_abstract_mesh()
    n = stream_shards(mesh, *h.shape[:2])
    if n == 1:
        plain = [jnp.einsum(equation, h, w.astype(h.dtype)) for equation, w in matmuls]
        return plain if ordered else [(y,) for y in plain]
    equations = [equation for equation, _ in matmuls]
    specs = [_specs(equation, mesh) for equation in equations]
    seq_axes = [equation.split("->")[1].index("s") for equation in equations]

    def local(piece, *weights):
        me = jax.lax.axis_index("tp")
        piece = _after_small_weights(piece, weights)
        made = []
        for step in range(n):
            made.append([jnp.einsum(equation, piece, w) for equation, w in zip(equations, weights)])
            if step < n - 1:
                piece = jax.lax.ppermute(piece, "tp", _ring(n))
        if not ordered:
            return [tuple(pieces) for pieces in zip(*made)]
        # place j of the sequence holds the rows of device j: the piece of step (me - j) % n. A
        # select and a concatenate are row-wise work that fuses into whatever reads the result
        return [jnp.concatenate([jax.lax.select_n((me - j) % n, *pieces) for j in range(n)], axis)
                for axis, pieces in zip(seq_axes, zip(*made))]

    out_specs = [s[2] if ordered else (s[2],) * n for s in specs]
    return jax.shard_map(
        local, mesh=mesh, in_specs=(specs[0][0], *(s[1] for s in specs)), out_specs=out_specs,
        check_vma=True,
    )(h, *(w.astype(h.dtype) for _, w in matmuls))


def row_parallel(equation: str, a: Any, w: jax.Array, ordered: bool = True) -> jax.Array:
    """einsum(equation, a, w) -> (B, S, E): a with whole sequences and its
    features over `tp`, w, cast to a's dtype, split over `tp` along the
    features it contracts (`bhsd,hde->bse`, `bsf,fe->bse`). Where the
    stream's sequence lies over `tp` the partial sums of one piece of the rows
    at a time are added to the sum that goes round the ring and ends on the
    device that holds those rows: a reduce-scatter, each transfer beside the
    next piece's matmul. `ordered=False`: a is the tuple of pieces that
    `column_parallel(..., ordered=False)` gave, after row-wise work."""
    mesh = jax.sharding.get_abstract_mesh()
    axis = equation.index("s")    # in the activation, the first operand
    first = a if ordered else a[0]
    n = stream_shards(mesh, first.shape[0], first.shape[axis]) if ordered else len(a)
    w = w.astype(first.dtype)
    if n == 1:
        return jnp.einsum(equation, first, w)
    a_spec, w_spec, out_spec = _specs(equation, mesh)

    def local(a, w):
        me = jax.lax.axis_index("tp")
        if ordered:      # the rows of device j, by a select that fuses into the matmul that reads them
            rows = a.shape[axis] // n
            blocks = [jax.lax.slice_in_dim(a, j * rows, (j + 1) * rows, axis=axis) for j in range(n)]
        total = None
        for step in range(n):
            # the rows of the device `step` + 1 hops up the ring: the sum reaches it as the ring closes
            block = jax.lax.select_n((me - 1 - step) % n, *blocks) if ordered else a[(step + 1) % n]
            partial = jnp.einsum(equation, block, w)
            if total is None:
                total = partial
            else:
                # the barrier keeps the compiler from fusing the sum into this piece's matmul, which
                # would then wait for the transfer it is there to cover
                total = jax.lax.ppermute(total, "tp", _ring(n)) + jax.lax.optimization_barrier(partial)
        return total

    return jax.shard_map(
        local, mesh=mesh, in_specs=(a_spec if ordered else (a_spec,) * n, w_spec), out_specs=out_spec,
        check_vma=True)(a if ordered else tuple(a), w)
