"""The LAD / Com-LAD meta-algorithm (Algorithms 1 and 2).

This module is the *protocol* layer: given per-subset gradients, it performs
one full round — task assignment, eq.-(5) encoding, compression, Byzantine
corruption, robust aggregation — and returns the global update direction.

``protocol_round`` is one vectorized round over the N logical devices' full
``(N, P)`` gradient stack (``lad_round`` computes that stack from a subset
gradient function first).  The grid engine (``core/engine.py``) and the LM
train step (``launch/train.py``) both run it.

``method``:
  * ``"lad"``   — Algorithm 1/2 (Com-LAD when ``compression.name != 'none'``)
  * ``"plain"`` — the non-redundant baselines (VA / CWTM / CWTM-NNM / Com-TGN):
                  equivalent to LAD with d = 1 (each device a single random
                  subset), per Section VII's fair-comparison setup.
  * ``"draco"`` — DRACO [13]: fractional repetition + majority-vote decode
                  (exact recovery; incompatible with compression).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import aggregators as agg_lib
from repro.core import attacks as attack_lib
from repro.core import compression as comp_lib
from repro.core import task_matrix as tm
from repro.core.participation import ParticipationSpec
from repro.kernels import ops as kernel_ops
from repro.numerics import stable_masked_mean0

__all__ = [
    "ProtocolConfig",
    "lad_round",
    "protocol_round",
    "make_attack_fn",
    "make_server_fn",
]


@dataclasses.dataclass(frozen=True)
class ProtocolConfig:
    """Static configuration of one protocol condition (Algorithms 1 and 2).

    This is the engine's compile-time contract: every field here shapes the
    compiled program (array sizes, branch structure, kernel choice), which is
    why the vmapped grid engine groups configs that differ in these fields
    into separate compile buckets.

    Attributes:
      n_devices: ``N`` — logical devices == data subsets (Section II).
      d: computational load — subsets computed per device per round (the
        cyclic task matrix's ones-per-row).  Ignored for ``method="plain"``
        (forced to 1) and the group size for ``method="draco"`` (needs
        ``d | N``).
      method: ``"lad"`` (Algorithm 1/2; Com-LAD when compression is on),
        ``"plain"`` (non-redundant baselines, d=1), or ``"draco"``
        (fractional repetition + majority-vote exact decode [13]).
      aggregator: any key of ``aggregators.AGGREGATORS``, optionally with the
        ``"-nnm"`` suffix for nearest-neighbor-mixing pre-aggregation.
      trim_frac: CWTM trim fraction (``f = floor(trim_frac * N)`` per side).
      n_byz: number of Byzantine devices ``N - H``.
      attack: the corruption model (see ``attacks.AttackSpec``).
      compression: the Com-LAD wire compression (Definition 2).
      participation: the erasure/straggler fault model
        (``participation.ParticipationSpec``).  The default ``"full"``
        schedule is a STATIC bypass — the round program is byte-identical to
        the pre-participation engine.  Any other schedule compiles the
        masked path: the per-round mask erases transmitted rows to exact
        ``0.0`` and the server becomes mask-aware (``aggregator="decode"``
        selects the cyclic K-of-N erasure decode; DRACO's decoder medians
        over reporting group members; every other aggregator sees erased
        rows imputed with the reporting-row mean so its breakdown analysis
        is over the ``K`` real reports).
      backend: hot-path kernel backend for the server/device inner ops
        (kernels/ops.py) — the eq.-(5) combine, CWTM, the NNM gram matrix
        and QSGD quantization:

          * ``"xla"``       — pure-jnp reference path (CPU default);
          * ``"interpret"`` — Pallas interpret mode (CPU-correct kernel
                              semantics, used by the parity tests);
          * ``"pallas"``    — compiled Pallas kernels (TPU target).

        The ops wrappers own the tiling contract: any ``Q`` is accepted —
        non-divisible lengths are zero-padded to the tile boundary and
        sliced back, bit-identical to the unpadded math on the real
        coordinates (zero columns are exact no-ops for every kernel).
    """

    n_devices: int
    d: int = 1  # computational load (subsets per device)
    method: str = "lad"  # lad | plain | draco
    aggregator: str = "cwtm"  # any key of aggregators.AGGREGATORS, opt. "-nnm"
    trim_frac: float = 0.1
    n_byz: int = 0
    attack: attack_lib.AttackSpec = dataclasses.field(
        default_factory=lambda: attack_lib.AttackSpec(name="sign_flip")
    )
    compression: comp_lib.CompressionSpec = dataclasses.field(
        default_factory=comp_lib.CompressionSpec
    )
    participation: ParticipationSpec = dataclasses.field(
        default_factory=ParticipationSpec
    )
    backend: str = "xla"

    def make_aggregator(self):
        return agg_lib.make_aggregator(
            self.aggregator, n_byz=self.n_byz, trim_frac=self.trim_frac
        )

    def effective_d(self) -> int:
        return 1 if self.method == "plain" else self.d


def _encode(cfg: ProtocolConfig, stacked: jax.Array) -> jax.Array:
    """eq.-(5) per-device combine of the assigned ``(N, d, Q)`` rows (XLA
    path).  On long rows the operand is a stack of selects that XLA fuses
    into this reduce, so no ``(N, d, Q)`` buffer is written; kernel backends
    use ``kernel_ops.gather_combine`` instead."""
    del cfg
    return jnp.mean(stacked, axis=1)


def _device_coded_gradients(cfg: ProtocolConfig, key: jax.Array, subset_grads: jax.Array):
    """Assemble the (N, Q) stack of honest coded vectors g_i^t (eq. 5).

    Returns ``(coded, subsets, assign)``: ``assign`` is the decoder-facing
    structure of this round's allocation — the ``(N,)`` cyclic window starts
    (``TaskAssignment.task_index``) for lad/plain, the ``(N,)`` group ids for
    draco — which the participation-masked servers need (the K-of-N erasure
    decode selects a surviving offset class by ``task_index % d``).
    """
    n = cfg.n_devices
    d = cfg.effective_d()
    if cfg.method == "draco":
        # fractional repetition: device i's group replicates a permuted block
        perm = jax.random.permutation(key, n)
        groups = jnp.arange(n) // d  # (N,)
        block_cols = groups[:, None] * d + jnp.arange(d)[None, :]  # (N, d)
        subsets = perm[block_cols]
        assign = groups.astype(jnp.int32)
    else:
        ta = tm.sample_assignment(key, n, d)
        subsets = ta.subsets
        assign = ta.task_index.astype(jnp.int32)
    # The encode's form, from static shapes.  Short rows gather.  Long rows
    # avoid the gather, which XLA:TPU compiles in time that grows with the
    # row length: d = 1 slices the rows (a permutation XLA folds into the
    # server's reduce), d > 1 selects them.
    if cfg.backend != "xla":
        path = "kernel"
    elif subset_grads.shape[-1] < _LONG_ROW:
        path = "gather"
    elif d == 1:
        path = "slice"
    else:
        path = "select"
    _ENCODE_TRACES[path] += 1  # runs at trace time only
    if path == "kernel":
        # kernel hot path: assignment gather + eq.-(5) combine fused into one
        # lane-batched launch (under the grid engine's vmap a lane is one
        # scenario; the device axis stays inside the kernel block), so no
        # (N, d, Q) gathered stack ever materializes in XLA
        w = jnp.full((d,), 1.0 / d, jnp.float32)
        return (
            kernel_ops.gather_combine(subset_grads, subsets, w, backend=cfg.backend),
            subsets,
            assign,
        )
    if path == "select":
        return _encode(cfg, _select_rows(subset_grads, subsets)), subsets, assign
    return _encode(cfg, _gather_rows(subset_grads, subsets)), subsets, assign


# Row length from which the XLA encode stops gathering rows.
_LONG_ROW = 1 << 16

# Trace-time count of the encode form each traced program took.
_ENCODE_TRACES = {"select": 0, "slice": 0, "gather": 0, "kernel": 0}


def encode_path_info() -> dict:
    """{select, slice, gather, kernel}: how many traced programs took each
    form of the eq.-(5) encode (``_device_coded_gradients``)."""
    return dict(_ENCODE_TRACES)


def _gather_rows(x: jax.Array, idx: jax.Array) -> jax.Array:
    """``x[idx]`` for an ``(N, Q)`` stack and an ``(N, d)`` index table.

    Long rows take ``N * d`` unrolled dynamic row slices: a gather of whole
    rows compiles in time that grows with the row length on XLA:TPU (30 s at
    ``Q = 2^25``), the slices in about a second.  Short rows (the
    linear-regression grids: ``Q ~ 100``, ``N * d`` up to thousands) keep
    the gather.  Both give the same rows bit for bit."""
    if x.shape[-1] < _LONG_ROW:
        return x[idx]
    return jnp.stack([
        jnp.stack([jax.lax.dynamic_index_in_dim(x, i, keepdims=False) for i in row])
        for row in idx
    ])


def _select_rows(x: jax.Array, idx: jax.Array) -> jax.Array:
    """``x[idx]`` for an ``(N, Q)`` stack and an ``(M, d)`` index table, by
    selection alone: each output element is one of the ``N`` candidates, so
    the rows are bit for bit those of the gather (signed zeros and NaN
    payloads included).  XLA fuses the selects into their consumer, the
    eq.-(5) reduce, so the ``(M, d, Q)`` result is never written; XLA:TPU
    copies each candidate row once to a 1-D layout first.  The candidates
    are taken from an ``(N, 1, Q)`` view, whose rows are contiguous in the
    layout an all-gathered stack arrives in, so that stack is not relaid
    out as a whole before the row copies."""
    n, q = x.shape
    rows = x.reshape(n, 1, q)
    shape = idx.shape + (q,)
    which = jnp.broadcast_to(idx.astype(jnp.int32)[..., None], shape)
    return jax.lax.select_n(which, *(jnp.broadcast_to(rows[j], shape) for j in range(n)))


def _full_server_fn(cfg: ProtocolConfig) -> Callable[[jax.Array], jax.Array]:
    """The full-participation server body ``(N, Q) -> (Q,)`` (see
    ``make_server_fn``)."""
    if cfg.method == "draco":
        return lambda transmitted: coded_draco_decode(transmitted, cfg.d)
    if cfg.backend != "xla":
        name, nnm = cfg.aggregator, False
        if name.endswith("-nnm"):
            name, nnm = name[: -len("-nnm")], True
        if name == "cwtm":

            def kernel_server(transmitted: jax.Array) -> jax.Array:
                msgs = transmitted
                if nnm:
                    d2 = kernel_ops.pairwise_sqdist(msgs, backend=cfg.backend)
                    msgs = agg_lib.nnm_mix(msgs, cfg.n_byz, d2=d2)
                trim = int(cfg.trim_frac * msgs.shape[0])
                return kernel_ops.cwtm(msgs, trim, backend=cfg.backend)

            return kernel_server
    return cfg.make_aggregator()


def _masked_server_fn(cfg: ProtocolConfig) -> Callable:
    """The participation-aware server ``(transmitted, pmask, assign) -> (Q,)``.

    Three regimes:
      * ``aggregator="decode"`` — the cyclic K-of-N erasure decode
        (``coding.cyclic_erasure_decode``): exact recovery of the
        full-participation gradient mean while erasures stay within the
        redundancy margin ``d - 1``; graceful partial mean beyond it.
        Requires the cyclic code (method lad/plain) and ``d | N``.
      * ``method="draco"`` — DRACO's group median over *reporting* members
        (``coding.draco_decode`` with a mask).
      * anything else — impute-then-aggregate: erased rows are replaced by
        the reporting-row mean (``numerics.stable_masked_mean0``) and the
        untouched full-participation rule runs on the patched stack, so the
        robust rule's order statistics only ever see ``K`` real values plus
        neutral fill.  At an all-ones mask the ``where`` select is an exact
        no-op and the base rule receives a bit-identical stack — the
        mechanism behind the all-ones == legacy bitwise regression tests.
    """
    if cfg.aggregator == "decode":
        if cfg.method == "draco":
            raise ValueError(
                "aggregator='decode' is the cyclic erasure decode — "
                "incompatible with method='draco' (use its own masked decoder)"
            )
        d = cfg.effective_d()
        if cfg.n_devices % d != 0:
            raise ValueError(
                f"aggregator='decode' exactness needs d | N (the offset "
                f"classes must tile the subset circle): N={cfg.n_devices} d={d}"
            )
        from repro.core.coding import cyclic_erasure_decode

        return lambda t, pm, assign: cyclic_erasure_decode(
            t, pm, assign, d, backend=cfg.backend
        )
    if cfg.method == "draco":
        return lambda t, pm, assign: coded_draco_decode(t, cfg.d, mask=pm)
    base = _full_server_fn(cfg)

    def masked_server(t: jax.Array, pm: jax.Array, assign: jax.Array) -> jax.Array:
        del assign
        imputed = stable_masked_mean0(t, pm)
        return base(jnp.where(pm[:, None] > 0.0, t, imputed[None, :]))

    return masked_server


@functools.lru_cache(maxsize=256)
def make_server_fn(cfg: ProtocolConfig) -> Callable:
    """Build the server aggregation for ``cfg``.

    Full participation (the default): ``(N, Q) transmitted -> (Q,)``, routed
    through the Pallas kernels when the config selects a kernel backend and
    the rule has a kernel realization (CWTM and its NNM-premixed variant —
    the paper's main rules); other rules fall back to the pure-jnp
    aggregators on every backend.  For DRACO the server is the group
    majority-vote decoder (compression-free exact recovery).

    Active participation (``cfg.participation.active``): the signature
    widens to ``(transmitted, pmask, assign) -> (Q,)`` — see
    ``_masked_server_fn`` for the three masked regimes.

    This is the branch unit of the vmapped grid engine: ``run_grid`` builds
    one server fn per distinct aggregator in a compile bucket and selects
    per-lane with ``lax.switch``.
    """
    if cfg.participation.active:
        return _masked_server_fn(cfg)
    if cfg.aggregator == "decode":
        raise ValueError(
            "aggregator='decode' (the K-of-N erasure decode) requires an "
            "active participation schedule — at full participation use the "
            "mean server (they recover the same gradient mean)"
        )
    return _full_server_fn(cfg)


@functools.lru_cache(maxsize=256)
def make_attack_fn(cfg: ProtocolConfig) -> attack_lib.Attack:
    """The corruption map ``(key, msgs, mask) -> transmitted`` of ``cfg``
    (attack spec with the config's Byzantine count folded in) — the second
    branch unit of the vmapped grid engine.

    Both factories are lru-cached on the (hashable, frozen) config so equal
    configs return the *same function object* across calls — the identity
    the grid engine's program cache keys its compiled executables on.

    On kernel backends the paper's attack menu (sign-flip, ALIE, IPM) is
    realized as lane-batched ``(lane, q_tile)`` kernels (see
    ``attacks.make_attack`` — incl. the measured interpret-mode scope note:
    collusion attacks ride the kernels on ``backend="pallas"`` only).
    """
    return dataclasses.replace(cfg.attack, n_byz=cfg.n_byz).make(backend=cfg.backend)


def protocol_round(
    cfg: ProtocolConfig,
    key: jax.Array,
    subset_grads: jax.Array,
    *,
    attack_fn: attack_lib.Attack | None = None,
    server_fn: Callable[[jax.Array], jax.Array] | None = None,
    participation_mask: jax.Array | None = None,
) -> jax.Array:
    """One full protocol round.

    Args:
      cfg: protocol configuration.
      key: round PRNG key (folds in the step index at the caller).
      subset_grads: ``(N, Q)`` — gradient of every logical data subset at the
        current iterate (the simulation's stand-in for devices' local compute).
      attack_fn / server_fn: optional overrides for the corruption map and the
        server aggregation.  ``None`` (the default) derives both from ``cfg``
        via ``make_attack_fn`` / ``make_server_fn``; the vmapped grid engine
        passes ``lax.switch``-dispatched versions so the attack/aggregator
        axes of a sweep become *traced* (one compile per static bucket, not
        per cell).
      participation_mask: ``(N,)`` 0/1 float mask of reporting devices —
        requires ``cfg.participation.active``.  The engine samples it from
        the schedule per round; the multi-process fleet passes its observed
        timeout mask (schedule ``"external"``).  ``None`` with an active
        schedule means all devices report *through the masked machinery*.
        Erased rows are zeroed AFTER the attack (an omniscient adversary's
        collusion statistics see the pre-erasure stack; a crashed attacker
        still sends nothing) and the mask-aware server decodes the
        survivors.

    Returns:
      ``(Q,)`` the aggregated global update direction ``g^t``.
    """
    n = cfg.n_devices
    if participation_mask is not None and not cfg.participation.active:
        raise ValueError(
            "participation_mask passed but cfg.participation is 'full' — "
            "select an active schedule (ParticipationSpec) so the masked "
            "server path is compiled"
        )
    k_assign, k_mask, k_attack, k_comp = jax.random.split(key, 4)

    # each stage runs under a ``lad.*`` named scope: HLO ``op_name`` metadata
    # only, so a device trace can attribute the round's time by stage
    with jax.named_scope("lad.encode"):
        coded, _, assign = _device_coded_gradients(cfg, k_assign, subset_grads)

    # --- Com-LAD compression (Definition 2) --------------------------------
    q = coded.shape[1]
    spec = cfg.compression
    if spec.name not in ("none", "identity"):
        with jax.named_scope("lad.compress"):
            if spec.name == "quant" and cfg.backend != "xla":
                # kernel hot path: the rounding randomness u is drawn per device
                # from its round key and fed to the fused quantize kernel — one
                # lane-batched launch over the device axis
                dev_keys = jax.random.split(k_comp, n)
                u = jax.vmap(lambda k: jax.random.uniform(k, (q,)))(dev_keys)
                coded = kernel_ops.stochastic_quantize(
                    coded, u, spec.levels, spec.chunk, backend=cfg.backend
                )
            else:
                # single compression stage shared with the fleet's workers
                # (compress_rows slices the same per-device key fan-out), so
                # worker-side compression is bit-identical to this path
                coded = comp_lib.compress_rows(spec, k_comp, coded, n_total=n)

    # --- Byzantine corruption ----------------------------------------------
    with jax.named_scope("lad.attack"):
        mask = attack_lib.sample_byzantine_mask(
            k_mask, n, cfg.n_byz, fixed=cfg.attack.fixed_identity
        )
        attack = attack_fn if attack_fn is not None else make_attack_fn(cfg)
        transmitted = attack(k_attack, coded, mask)

    # --- Server aggregation ------------------------------------------------
    # (For DRACO the server is the majority-vote decoder; it ignores
    # compression — incompatible, per Section VII.B.)
    server = server_fn if server_fn is not None else make_server_fn(cfg)
    with jax.named_scope("lad.aggregate"):
        if cfg.participation.active:
            # --- Participation erasure (after the attack, before the server) ---
            pm = (
                participation_mask
                if participation_mask is not None
                else jnp.ones((n,), jnp.float32)
            )
            # erased rows become exact 0.0 (x * 1.0 is bitwise-exact on the rest)
            transmitted = transmitted * pm[:, None]
            return server(transmitted, pm, assign)
        return server(transmitted)


def coded_draco_decode(
    transmitted: jax.Array, d: int, mask: jax.Array | None = None
) -> jax.Array:
    from repro.core.coding import draco_decode

    return draco_decode(transmitted, d, mask=mask)


def lad_round(
    cfg: ProtocolConfig,
    key: jax.Array,
    params: jax.Array,
    subset_grad_fn: Callable[[jax.Array], jax.Array],
) -> jax.Array:
    """Convenience wrapper: compute all subset gradients at ``params`` then run
    a protocol round.  ``subset_grad_fn(params) -> (N, Q)``."""
    return protocol_round(cfg, key, subset_grad_fn(params))
