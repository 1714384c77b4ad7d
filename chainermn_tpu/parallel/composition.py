"""Topology-composed collective schedules — the composition DSL.

The reference hand-wrote ONE reduction pipeline per topology class
(``two_dimensional_communicator.py`` (dagger): intra reduce-scatter ->
inter allreduce -> intra all-gather, fixed) and our schedule layer
started the same way: a three-entry menu (``flat`` / ``two_level`` /
``zero``). HiCCL (arXiv:2408.05962) and The Big Send-off
(arXiv:2504.18658) make the case that the winning schedule should be
COMPOSED from primitives per topology level — on a 3-level
``(dcn, ici_y, ici_x)`` mesh the menu cannot even express the best
pipeline (e.g. the per-level ladder ``rs(ici_x) > rs(ici_y) > ar(dcn) >
ag(ici_y) > ag(ici_x)``), and an autotuner can only search what its
candidate set contains.

This module is that generalisation, in three pieces:

- a tiny DSL: a :class:`Composition` is an ordered tuple of
  :class:`Stage` s, each ``(primitive x axis-subset)`` with primitives
  ``reduce_scatter`` / ``allreduce`` / ``allgather`` /
  ``sharded_update`` (the ZeRO fuse point, arXiv:2004.13336). Each
  composition prints as a stable signature string
  (``"rs(a2)>ar(a0+a1)>ag(a2)"``) — the spelling the autotune registry,
  trace ``wire`` events and bench rows all key on;
- a VALIDATOR (:func:`validate_composition`) that proves a composition
  is a correct mean-allreduce *before* anything runs: every element
  reduced over every mesh axis exactly once, every scatter conjugated
  by a gather (LIFO, same axis group), the sharded-update placed at the
  fully-reduced shard. Violations raise :class:`CompositionError`
  naming the broken invariant;
- a DERIVER (:func:`derive_compositions`) that enumerates the legal
  reduction compositions for an arbitrary n-level mesh (per-level
  rs->ar->ag ladders, axis-merged variants, slow-axis-innermost
  orderings — ``2^k`` compositions for ``k`` axes), so schedules for
  new topologies are generated, not hand-written. The old menu entries
  are DERIVED INSTANCES: ``flat`` is ``ar(all)``, ``two_level`` is
  ``rs(fast) > ar(rest) > ag(fast)``, and ``zero`` is
  ``rs(fast) > ar(rest) > su > ag(fast)`` (``rs(all) > su > ag(all)``
  on a flat mesh).

Execution is :func:`reduce_composed` — the ONE executor every schedule
(menu name or derived signature) compiles down to, inside the named-
axis context. Its per-stage primitives are exactly the collectives the
signature predicts (:func:`predicted_collectives`), which is what the
structural HLO-count tests pin (``tests/test_composition.py``).

BUCKET SLICING (ISSUE 15): every stage is additionally addressable on a
SLICE of the bucket. A composition with ``slices=S`` cuts the bucket
into S equal contiguous slices (:func:`slice_bounds`; a bucket smaller
than S degrades to ``min(S, elements)`` slices — the
``bucket_partition`` zero-leaf contract, never an empty stage) and
software-pipelines the stages across them in skewed order
(:func:`expand_slices`): slice i's slow inter-level stage (e.g.
``ar(a0+a1)``) is issued concurrently with slice i+1's fast-axis
``rs``/``ag`` — the classic hierarchical-allreduce interleave, so the
slow axis hides behind the fast one. Spelled ``rs(a2)[s0..3]>
ar(a0+a1)>ag(a2)`` (the slice range rides the first stage); an
individual expanded stage prints as ``rs(a2)[s1:4]`` (slice 1 of 4).
The compiled HLO carries exactly S× the per-stage collective count at
1/S payload each — total wire bytes unchanged — and every sliced
composition is bitwise == its flat rendering on exact-dyadic inputs
(slices partition the bucket disjointly; each element is still reduced
over every mesh axis exactly once). The ``sharded_update`` fuse point
is unsliceable (the inner optimizer runs ONCE on the whole chunk
tree), refused loudly by the validator.

Mesh-axis convention: the tuple is in MESH ORDER, slow/DCN-most first,
fast/ICI-most last (the repo's convention) — so "scatter the fast axes
first, reduce the slow axis innermost" is "partition the reversed axis
tuple".
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Callable, Mapping, Optional, Sequence

PyTree = Any

#: Stage primitives. ``sharded_update`` is the ZeRO fuse point: the
#: caller's update function runs on the fully-reduced 1/n shard.
#: ``broadcast`` (ISSUE 16) is the one-to-many multicast-tree stage:
#: the merged group's root fans its buffer out over a radix-r tree of
#: ``ppermute`` rounds — the device-mesh rendering of the serving
#: plane's tree push (multicast-tree collectives, arXiv:2605.22428).
PRIMITIVES = ("reduce_scatter", "allreduce", "allgather", "sharded_update",
              "broadcast")

_SHORT = {"reduce_scatter": "rs", "allreduce": "ar", "allgather": "ag",
          "sharded_update": "su", "broadcast": "bc"}
_LONG = {v: k for k, v in _SHORT.items()}

#: HLO op a stage lowers to (the vocabulary of the structural tests;
#: ``sharded_update`` owes the wire nothing). A ``broadcast`` stage
#: lowers to ``tree_sends(n, radix)`` collective-permutes, not one op —
#: :func:`predicted_collectives` multiplies the sub-sends in.
STAGE_HLO = {"reduce_scatter": "reduce-scatter", "allreduce": "all-reduce",
             "allgather": "all-gather", "broadcast": "collective-permute"}

#: Default multicast-tree radix (binary tree: doubling rounds).
DEFAULT_RADIX = 2


def tree_depth(n: int, radix: int = DEFAULT_RADIX) -> int:
    """Rounds a radix-``radix`` multicast tree needs to cover ``n``
    members from one root: ``ceil(log_radix(n))``, computed by the same
    holder-doubling walk the executor runs so the two can never
    disagree. The HLO collective-permute count of a ``bc`` stage, the
    donor-send depth of the serving tree push."""
    n, r = int(n), int(radix)
    if r < 2:
        raise CompositionError(f"multicast radix must be >= 2, got {radix}")
    d, holders = 0, 1
    while holders < n:
        holders *= r
        d += 1
    return d


def tree_sends(n: int, radix: int = DEFAULT_RADIX) -> int:
    """``ppermute`` ops a radix-``radix`` multicast over ``n`` members
    lowers to. A ppermute's sources must be unique, so each holder-
    doubling round decomposes into up to ``radix - 1`` sub-sends
    (holder ``s`` -> ``s + j*holders``, one ppermute per ``j``) — at
    radix 2 this equals :func:`tree_depth`; a larger radix trades
    rounds for per-round sends (``(r-1)*ceil(log_r(n))`` at full
    occupancy). The per-stage HLO collective-permute count
    :func:`predicted_collectives` pins."""
    n, r = int(n), int(radix)
    if r < 2:
        raise CompositionError(f"multicast radix must be >= 2, got {radix}")
    sends, holders = 0, 1
    while holders < n:
        for j in range(1, r):
            if j * holders < n:  # sub-send j has at least sender s=0
                sends += 1
        holders *= r
    return sends


class CompositionError(ValueError):
    """A composition failed validation; the message names the broken
    invariant."""


@dataclasses.dataclass(frozen=True)
class Stage:
    """One pipeline stage: ``primitive`` over the merged axis group
    ``axes`` (mesh-order tuple; empty only for ``sharded_update``).

    ``slice`` (ISSUE 15) addresses the stage at ONE slice of the
    bucket: ``(index, n_slices)``, printed ``rs(a2)[s1:4]``. ``None``
    = the whole bucket (the pre-slicing spelling, unchanged). Slice-
    annotated stages appear in the EXPANDED rendering of a sliced
    composition (:func:`expand_slices`); the compact spelling keeps the
    slice count on the :class:`Composition` instead.

    ``radix`` (ISSUE 16) is the multicast-tree fan-out of a
    ``broadcast`` stage (``None`` = :data:`DEFAULT_RADIX`); printed
    only when non-default (``bc(a0+a1)@4``). Reduction stages carry no
    radix — the validator refuses one."""

    primitive: str
    axes: tuple[str, ...] = ()
    slice: Optional[tuple[int, int]] = None
    radix: Optional[int] = None

    def signature(self) -> str:
        tag = f"[s{self.slice[0]}:{self.slice[1]}]" if self.slice else ""
        if self.primitive == "sharded_update":
            return f"su{tag}"
        rad = (f"@{self.radix}"
               if self.radix is not None and self.radix != DEFAULT_RADIX
               else "")
        return f"{_SHORT[self.primitive]}({'+'.join(self.axes)}){rad}{tag}"


@dataclasses.dataclass(frozen=True)
class Composition:
    """An ordered stage list; build via :func:`parse_signature`,
    :func:`compile_schedule` or :func:`derive_compositions`, then prove
    it with :func:`validate_composition` before running it.

    ``slices`` (ISSUE 15): the bucket-slice count the executor cuts
    each bucket into (1 = the whole-bucket rendering, unchanged).
    Spelled by annotating the FIRST stage with the slice range:
    ``rs(a2)[s0..3]>ar(a0+a1)>ag(a2)`` is the two_level pipeline over
    four bucket slices.

    ``slice_layout`` (ISSUE 16 satellite): how the bucket is cut —
    ``'contiguous'`` (ISSUE 15's balanced runs) or ``'zigzag'``
    (strided: slice i takes elements ``i, i+S, i+2S, ...``, so every
    slice samples the whole bucket uniformly and the gather tails stay
    interleave-balanced at extreme S). Spelled with a ``z`` range tag:
    ``rs(a2)[z0..3]>ar(a0+a1)>ag(a2)``. Per-slice element counts are
    identical to contiguous (first ``n % S`` slices one longer), so
    wire layout and HLO counts do not move — only the cut/reassembly
    indexing does, and both layouts are bitwise-equal reductions."""

    stages: tuple[Stage, ...]
    slices: int = 1
    slice_layout: str = "contiguous"

    def signature(self) -> str:
        sigs = [s.signature() for s in self.stages]
        if self.slices > 1 and sigs:
            letter = "z" if self.slice_layout == "zigzag" else "s"
            sigs[0] = f"{sigs[0]}[{letter}0..{self.slices - 1}]"
        return ">".join(sigs)

    @property
    def has_update(self) -> bool:
        return any(s.primitive == "sharded_update" for s in self.stages)

    def split_update(self) -> tuple[tuple[Stage, ...], tuple[Stage, ...]]:
        """``(reduce_prefix, gather_suffix)`` around the
        ``sharded_update`` stage — the seam the ZeRO executors use (the
        inner optimizer runs BETWEEN them, once, on the whole chunk
        tree)."""
        for i, s in enumerate(self.stages):
            if s.primitive == "sharded_update":
                return self.stages[:i], self.stages[i + 1:]
        raise CompositionError(
            f"composition {self.signature()!r} has no sharded_update "
            "stage to split at"
        )

    def __str__(self) -> str:  # pragma: no cover - convenience
        return self.signature()


_STAGE_RE = re.compile(
    r"^(rs|ar|ag|su|bc)(?:\(([^()]*)\))?(?:@(\d+))?"
    r"(?:\[([sz])(\d+)(?:\.\.(\d+)|:(\d+))?\])?$"
)


def parse_signature(sig: str) -> Composition:
    """Parse ``"rs(a2)>ar(a0+a1)>ag(a2)"`` back into a
    :class:`Composition` (the registry stores winners as signature
    strings; this is the way back). Two slice spellings (ISSUE 15):
    a range ``rs(a2)[s0..3]>...`` marks the whole COMPOSITION sliced
    (S = range length, must start at s0; annotations on several stages
    must agree), and ``rs(a2)[s1:4]`` addresses one expanded stage at
    slice 1 of 4. A ``z`` range (``rs(a2)[z0..3]``, ISSUE 16) selects
    the zigzag slice layout — composition-level only, expanded stages
    always address contiguous slices. ``bc(a0+a1)@4`` (ISSUE 16) is a
    radix-4 multicast-tree broadcast stage (``@2`` is the default and
    never printed)."""
    stages = []
    slices: Optional[int] = None
    layout: Optional[str] = None
    for part in str(sig).split(">"):
        m = _STAGE_RE.match(part.strip())
        if not m:
            raise CompositionError(
                f"unparseable composition stage {part!r} in {sig!r} "
                "(expected e.g. 'rs(intra)', 'ar(a0+a1)', 'su', "
                "'bc(a0)@4', 'rs(a2)[s0..3]', 'rs(a2)[z0..3]', "
                "'rs(a2)[s1:4]')"
            )
        short, axes, radix, letter, s_lo, s_hi, s_tot = m.groups()
        if radix is not None and short != "bc":
            raise CompositionError(
                f"stage {part!r}: only broadcast (bc) stages carry a "
                "multicast radix"
            )
        stage_slice: Optional[tuple[int, int]] = None
        if s_lo is not None:
            if s_tot is not None:  # [sI:S] — one expanded stage
                if letter == "z":
                    raise CompositionError(
                        f"stage {part!r}: zigzag is a composition-level "
                        "slice layout — expanded stages address slices "
                        "with [sI:S]"
                    )
                idx, tot = int(s_lo), int(s_tot)
                if not 0 <= idx < tot:
                    raise CompositionError(
                        f"stage slice [s{idx}:{tot}] in {part!r} is out "
                        "of range"
                    )
                stage_slice = (idx, tot)
            else:  # [s0..N] / [z0..N] (or degenerate) — the composition
                lo = int(s_lo)
                hi = int(s_hi) if s_hi is not None else lo
                if lo != 0 or hi < lo:
                    raise CompositionError(
                        f"composition slice range [{letter}{lo}..{hi}] in "
                        f"{part!r} must start at {letter}0"
                    )
                n = hi + 1
                if slices is not None and slices != n:
                    raise CompositionError(
                        f"conflicting slice counts in {sig!r}: "
                        f"{slices} vs {n}"
                    )
                this_layout = "zigzag" if letter == "z" else "contiguous"
                if layout is not None and layout != this_layout:
                    raise CompositionError(
                        f"conflicting slice layouts in {sig!r}: "
                        f"{layout} vs {this_layout}"
                    )
                slices = n
                layout = this_layout
        if short == "su":
            if axes:
                raise CompositionError(
                    f"sharded_update stage carries no axes, got {part!r}"
                )
            stages.append(Stage("sharded_update", slice=stage_slice))
        else:
            names = tuple(a for a in (axes or "").split("+") if a)
            # an explicit @2 normalizes to the default-radix spelling
            # (signatures stay canonical: parse(sig).signature() == sig)
            r = int(radix) if radix is not None else None
            stages.append(Stage(
                _LONG[short], names, slice=stage_slice,
                radix=(r if r != DEFAULT_RADIX else None),
            ))
    return Composition(tuple(stages), slices=slices or 1,
                       slice_layout=layout or "contiguous")


def canonical_axis_names(k: int) -> tuple[str, ...]:
    """Positional axis tokens ``('a0', ..., 'a<k-1>')`` — the spelling
    the WORLD-SHAPE-keyed tuning decision uses, so a cached winner is
    portable across communicators whose meshes name their axes
    differently (``bind_composition`` maps tokens back by position)."""
    return tuple(f"a{i}" for i in range(k))


def bind_composition(comp: Composition, axes: Sequence[str]) -> Composition:
    """Rebind a composition written over :func:`canonical_axis_names`
    onto the actual mesh ``axes`` by position. A composition already
    spelled in ``axes``'s names passes through unchanged."""
    names = tuple(axes)
    used = {a for s in comp.stages for a in s.axes}
    if used <= set(names):
        return comp
    canon = canonical_axis_names(len(names))
    if not used <= set(canon):
        raise CompositionError(
            f"composition {comp.signature()!r} names axes "
            f"{sorted(used - set(names))} that are neither on the mesh "
            f"{names} nor canonical positional tokens {canon}"
        )
    table = dict(zip(canon, names))
    return dataclasses.replace(comp, stages=tuple(
        dataclasses.replace(s, axes=tuple(table[a] for a in s.axes))
        for s in comp.stages
    ))


# ---------------------------------------------------------------------------
# Bucket slicing (ISSUE 15)
# ---------------------------------------------------------------------------


def effective_slices(slices: int, n_elems: int) -> int:
    """The slice count a bucket of ``n_elems`` elements actually cuts
    into: ``min(slices, n_elems)``, floored at 1 — a bucket smaller
    than the requested slice count DEGRADES instead of emitting an
    empty stage or a zero-size collective (the ``bucket_partition``
    zero-leaf contract, ISSUE 15 satellite; callers that degrade
    record the requested vs effective counts as provenance)."""
    s = int(slices)
    if s < 1:
        raise CompositionError(f"slices must be >= 1, got {slices}")
    return max(1, min(s, int(n_elems)))


def slice_bounds(n_elems: int, n_slices: int) -> list[tuple[int, int]]:
    """Balanced contiguous ``[start, end)`` bounds cutting ``n_elems``
    into ``n_slices`` slices (first ``n % S`` slices one element
    longer). The bounds are disjoint, cover the bucket exactly, and —
    given ``n_slices <= n_elems``, which :func:`effective_slices`
    guarantees — never empty: the structural half of the "every
    element reduced exactly once across slices" invariant."""
    n, s = int(n_elems), int(n_slices)
    if s < 1:
        raise CompositionError(f"slice count must be >= 1, got {n_slices}")
    base, rem = divmod(n, s)
    out = []
    lo = 0
    for i in range(s):
        hi = lo + base + (1 if i < rem else 0)
        out.append((lo, hi))
        lo = hi
    return out


def sliced_composition(comp: Composition, slices: int,
                       layout: str = "contiguous") -> Composition:
    """``comp`` re-rendered over ``slices`` bucket slices (the compact
    form — :func:`expand_slices` produces the per-slice stage list).
    Refuses a ``sharded_update`` pipeline: the ZeRO fuse point runs the
    inner optimizer ONCE on the whole chunk tree and cannot slice.
    ``layout`` (ISSUE 16 satellite) picks the cut: ``'contiguous'``
    runs or the ``'zigzag'`` stride (see :class:`Composition`)."""
    s = int(slices)
    if s < 1:
        raise CompositionError(f"slices must be >= 1, got {slices}")
    if layout not in ("contiguous", "zigzag"):
        raise CompositionError(
            f"slice layout must be 'contiguous' or 'zigzag', got "
            f"{layout!r}"
        )
    if s > 1 and comp.has_update:
        raise CompositionError(
            f"{comp.signature()!r}: a sharded_update pipeline cannot be "
            "sliced — the fuse point runs the inner optimizer once on "
            "the whole chunk tree"
        )
    return dataclasses.replace(comp, slices=s, slice_layout=layout)


def compact_slices(comp: Composition) -> Composition:
    """Reconstitute an EXPANDED composition (per-stage ``[sI:S]``
    addresses) back into the compact ``slices=S`` form the executors
    run — the inverse of :func:`expand_slices`. Unannotated
    compositions pass through unchanged. Every slice must run the SAME
    base pipeline (a heterogeneous expansion validates mathematically
    but has no compact rendering to execute) and the composition must
    have passed :func:`validate_composition` first — this only
    re-groups, it does not re-prove."""
    if not any(s.slice is not None for s in comp.stages):
        return comp
    per_slice: dict[int, list[Stage]] = {}
    total = 0
    for s in comp.stages:
        if s.slice is None:
            raise CompositionError(
                f"{comp.signature()!r}: stage {s.signature()!r} has no "
                "slice address while others do"
            )
        per_slice.setdefault(s.slice[0], []).append(
            dataclasses.replace(s, slice=None))
        total = max(total, s.slice[1])
    base = per_slice.get(0)
    if base is None or sorted(per_slice) != list(range(total)):
        raise CompositionError(
            f"{comp.signature()!r}: slice indices do not cover "
            f"0..{total - 1}"
        )
    for i, stages in per_slice.items():
        if stages != base:
            raise CompositionError(
                f"{comp.signature()!r}: slice s{i} runs a different "
                f"pipeline than slice s0 "
                f"({'>'.join(s.signature() for s in stages)} vs "
                f"{'>'.join(s.signature() for s in base)}) — only a "
                "uniform expansion has a compact executable rendering"
            )
    return Composition(tuple(base), slices=total)


def expand_slices(
    comp: Composition, size: Optional[int] = None
) -> tuple[Stage, ...]:
    """The sliced composition's per-slice stage list in SOFTWARE-
    PIPELINED (skewed) issue order: tick t issues stage j of slice i
    for every ``i + j == t`` (later slices first within a tick), so
    slice i's slow inter-level stage is in flight while slice i+1 runs
    its fast-axis stage — the interleave that lets the slow axis hide
    behind the fast one. Each emitted :class:`Stage` carries its
    ``slice=(i, S)`` address. ``size`` (bucket element count) applies
    the :func:`effective_slices` degrade; an unsliced composition
    expands to its own stages unchanged."""
    s_eff = (effective_slices(comp.slices, size) if size is not None
             else comp.slices)
    if s_eff <= 1:
        return comp.stages
    k = len(comp.stages)
    out: list[Stage] = []
    for t in range(s_eff + k - 1):
        for j in range(k):
            i = t - j
            if 0 <= i < s_eff:
                out.append(dataclasses.replace(
                    comp.stages[j], slice=(i, s_eff)))
    return tuple(out)


# ---------------------------------------------------------------------------
# Validator: prove the composition is a correct mean-allreduce
# ---------------------------------------------------------------------------


def validate_composition(
    comp: Composition, mesh_axes: Sequence[str]
) -> Composition:
    """Prove ``comp`` is a correct mean-allreduce over ``mesh_axes``
    BEFORE anything runs. Invariants (each violation raises
    :class:`CompositionError` naming it):

    - the stage list is non-empty and every primitive is known;
    - every reduce/scatter/gather stage names >= 1 mesh axis, no axis
      twice within a stage;
    - every mesh axis is REDUCED EXACTLY ONCE (by a ``reduce_scatter``
      or ``allreduce`` stage) — a missed axis leaves a partial sum, a
      doubled axis over-reduces;
    - scatters and gathers are CONJUGATE: each ``allgather`` closes the
      most recent open ``reduce_scatter`` with the SAME axis group
      (LIFO), and no scatter is left open at the end — otherwise the
      output shards don't reassemble to the input layout;
    - at most one ``sharded_update``, placed at the fully-reduced shard:
      after every reduction, before every gather, with at least one
      scatter open (otherwise the update is not sharded — that is the
      plain post-reduction update, not a composition stage).

    Sliced compositions (ISSUE 15) add:

    - ``slices`` is an integer >= 1; a sliced composition must not
      carry a ``sharded_update`` (the fuse point is unsliceable);
    - an EXPANDED composition (stages carrying ``slice`` addresses):
      every stage is addressed or none, all totals agree, every slice
      index 0..S-1 appears, and each slice's stage subsequence is
      independently a complete, conjugate mean-allreduce — PER-SLICE
      CONJUGACY. Together with :func:`slice_bounds`' disjoint cover,
      that is "every element reduced exactly once across slices".
    """
    mesh = tuple(mesh_axes)
    if not isinstance(comp, Composition):
        raise CompositionError(
            f"expected a Composition, got {type(comp).__name__}"
        )
    if not comp.stages:
        raise CompositionError(
            "empty stage list: a composition must reduce over "
            f"{mesh} and an empty pipeline reduces nothing"
        )
    if not isinstance(comp.slices, int) or comp.slices < 1:
        raise CompositionError(
            f"{comp.signature()!r}: slices must be an integer >= 1, "
            f"got {comp.slices!r}"
        )
    if comp.slice_layout not in ("contiguous", "zigzag"):
        raise CompositionError(
            f"{comp.signature()!r}: slice layout must be 'contiguous' "
            f"or 'zigzag', got {comp.slice_layout!r}"
        )
    sliced = [s for s in comp.stages if s.slice is not None]
    if comp.has_update and (comp.slices > 1 or sliced):
        raise CompositionError(
            f"{comp.signature()!r}: a sliced composition cannot carry a "
            "sharded_update stage — the ZeRO fuse point runs the inner "
            "optimizer once on the whole chunk tree and is unsliceable"
        )
    if sliced:
        if comp.slices > 1:
            raise CompositionError(
                f"{comp.signature()!r}: both a composition-level slice "
                f"count ({comp.slices}) and per-stage slice addresses — "
                "spell one form (compact slices= OR the expanded "
                "per-stage [sI:S] addressing), not both"
            )
        if len(sliced) != len(comp.stages):
            bare = next(s for s in comp.stages if s.slice is None)
            raise CompositionError(
                f"{comp.signature()!r}: stage {bare.signature()!r} has "
                "no slice address while others do — an expanded "
                "composition addresses every stage"
            )
        totals = {s.slice[1] for s in comp.stages}
        if len(totals) != 1:
            raise CompositionError(
                f"{comp.signature()!r}: conflicting slice totals "
                f"{sorted(totals)} — every stage of one expansion "
                "shares one slice count"
            )
        total = totals.pop()
        per_slice: dict[int, list[Stage]] = {}
        for s in comp.stages:
            per_slice.setdefault(s.slice[0], []).append(
                dataclasses.replace(s, slice=None))
        missing = [i for i in range(total) if i not in per_slice]
        if missing:
            raise CompositionError(
                f"{comp.signature()!r}: slice(s) {missing} have no "
                f"stages — {total} slices were addressed and each "
                "must run the full pipeline (its elements would "
                "otherwise never be reduced)"
            )
        for i in range(total):
            try:
                _validate_walk(
                    Composition(tuple(per_slice[i])), mesh
                )
            except CompositionError as e:
                raise CompositionError(
                    f"slice s{i}:{total}: {e}"
                ) from None
        return comp
    _validate_walk(comp, mesh)
    return comp


def _validate_walk(comp: Composition, mesh: tuple) -> Composition:
    """Route one pipeline's stage list to its family walk: a pipeline
    with any ``broadcast`` stage is the BROADCAST FAMILY (all stages
    bc — :func:`_validate_broadcast_walk`), everything else is the
    reduction family (:func:`_validate_stage_walk`). The two families
    never mix in one pipeline: a broadcast inside a reduction would
    overwrite partially-reduced shards with the root's, and a
    reduction inside a broadcast has nothing summed to reduce."""
    if any(s.primitive == "broadcast" for s in comp.stages):
        return _validate_broadcast_walk(comp, mesh)
    return _validate_stage_walk(comp, mesh)


def _validate_broadcast_walk(comp: Composition, mesh: tuple) -> Composition:
    """The broadcast-family walk (ISSUE 16): every stage is ``bc``,
    every mesh axis is broadcast EXACTLY ONCE (a missed axis leaves
    stale replicas, a doubled axis re-sends bytes the first tree
    already delivered), radix >= 2, no ``sharded_update`` (nothing is
    reduced, so there is no fully-reduced shard to fuse at)."""
    covered: list[str] = []
    for st in comp.stages:
        if st.primitive != "broadcast":
            raise CompositionError(
                f"{comp.signature()!r}: {st.signature()} mixed into a "
                "broadcast pipeline — bc stages never compose with "
                "reduction stages (the tree would overwrite partial "
                "sums with the root's buffer)"
            )
        if not st.axes:
            raise CompositionError(
                f"{comp.signature()!r}: broadcast stage with an empty "
                "axis group — every tree names the axes it fans over"
            )
        if len(set(st.axes)) != len(st.axes):
            raise CompositionError(
                f"{comp.signature()!r}: duplicate axis within stage "
                f"{st.signature()!r}"
            )
        for a in st.axes:
            if a not in mesh:
                raise CompositionError(
                    f"{comp.signature()!r}: axis {a!r} is not on the "
                    f"mesh {mesh}"
                )
            if a in covered:
                raise CompositionError(
                    f"{comp.signature()!r}: axis {a!r} broadcast more "
                    "than once — the second tree re-sends bytes the "
                    "first already delivered"
                )
        if st.radix is not None and st.radix < 2:
            raise CompositionError(
                f"{comp.signature()!r}: multicast radix must be >= 2, "
                f"got {st.radix}"
            )
        covered.extend(st.axes)
    missing = [a for a in mesh if a not in covered]
    if missing:
        raise CompositionError(
            f"{comp.signature()!r}: axes {tuple(missing)} never "
            "broadcast — those mesh levels would keep stale replicas"
        )
    return comp


def _validate_stage_walk(comp: Composition, mesh: tuple) -> Composition:
    """The per-stage invariant walk over ONE pipeline's stage list
    (:func:`validate_composition` runs it once for an unsliced/compact
    composition and once PER SLICE for an expanded one — per-slice
    conjugacy is literally the same walk)."""
    reduced: list[str] = []
    open_scatters: list[tuple[str, ...]] = []
    update_seen = False
    for st in comp.stages:
        if st.primitive not in PRIMITIVES:
            raise CompositionError(
                f"unknown primitive {st.primitive!r} (stages compose "
                f"{PRIMITIVES})"
            )
        if st.radix is not None:
            raise CompositionError(
                f"{comp.signature()!r}: stage {st.signature()!r} carries "
                "a multicast radix — only broadcast (bc) stages fan "
                "over a tree"
            )
        if st.primitive == "sharded_update":
            if update_seen:
                raise CompositionError(
                    f"{comp.signature()!r}: more than one sharded_update "
                    "stage — the ZeRO fuse point is single"
                )
            if set(reduced) != set(mesh):
                raise CompositionError(
                    f"{comp.signature()!r}: sharded_update before every "
                    f"axis is reduced (reduced {tuple(reduced)}, mesh "
                    f"{mesh}) — the update must see the fully-reduced "
                    "mean chunk"
                )
            if not open_scatters:
                raise CompositionError(
                    f"{comp.signature()!r}: sharded_update with no open "
                    "reduce_scatter — the update would not be sharded "
                    "(that is a plain post-reduction update, not a "
                    "composition stage)"
                )
            update_seen = True
            continue
        if not st.axes:
            raise CompositionError(
                f"{comp.signature()!r}: {st.primitive} stage with an "
                "empty axis group — every collective stage names the "
                "axes it rides"
            )
        if len(set(st.axes)) != len(st.axes):
            raise CompositionError(
                f"{comp.signature()!r}: duplicate axis within stage "
                f"{st.signature()!r}"
            )
        for a in st.axes:
            if a not in mesh:
                raise CompositionError(
                    f"{comp.signature()!r}: axis {a!r} is not on the "
                    f"mesh {mesh}"
                )
        if st.primitive in ("reduce_scatter", "allreduce"):
            if update_seen:
                raise CompositionError(
                    f"{comp.signature()!r}: {st.signature()} after the "
                    "sharded_update — every reduction precedes the fuse "
                    "point"
                )
            dup = [a for a in st.axes if a in reduced]
            if dup:
                raise CompositionError(
                    f"{comp.signature()!r}: axis {dup[0]!r} reduced more "
                    "than once — the mean would be over-divided"
                )
            reduced.extend(st.axes)
            if st.primitive == "reduce_scatter":
                open_scatters.append(st.axes)
        else:  # allgather
            if not open_scatters:
                raise CompositionError(
                    f"{comp.signature()!r}: {st.signature()} with no open "
                    "reduce_scatter to conjugate"
                )
            top = open_scatters.pop()
            if top != st.axes:
                raise CompositionError(
                    f"{comp.signature()!r}: {st.signature()} does not "
                    f"conjugate the open reduce_scatter over {top} — "
                    "scatter/gather pairs close LIFO with the same axis "
                    "group"
                )
    missing = [a for a in mesh if a not in reduced]
    if missing:
        raise CompositionError(
            f"{comp.signature()!r}: axes {tuple(missing)} never reduced "
            "— the result would not be the mean over the mesh"
        )
    if open_scatters:
        raise CompositionError(
            f"{comp.signature()!r}: reduce_scatter over "
            f"{open_scatters[-1]} never gathered back — the output "
            "would stay sharded"
        )
    return comp


def predicted_collectives(
    comp: Composition, size: Optional[int] = None,
    axis_sizes: Optional[Mapping[str, int]] = None,
) -> dict[str, int]:
    """HLO collective counts the compiled program must carry — one op
    per stage PER SLICE (``tests/test_composition.py`` compiles and
    compares): a sliced composition carries exactly S× the per-stage
    count at 1/S payload each. ``size`` (bucket element count) applies
    the :func:`effective_slices` degrade; without it the requested
    slice count is assumed achievable.

    A ``broadcast`` stage (ISSUE 16) lowers to ``tree_sends(n, radix)``
    collective-permutes, not one op, so its count needs the merged
    group size — pass ``axis_sizes`` (axis name -> size) for any
    composition carrying a bc stage; the ``"collective-permute"`` key
    appears ONLY then (reduction-only counts keep the exact three-key
    dict the structural tests compare against)."""
    s_eff = (effective_slices(comp.slices, size) if size is not None
             else comp.slices)
    out = {"reduce-scatter": 0, "all-reduce": 0, "all-gather": 0}
    if any(st.primitive == "broadcast" for st in comp.stages):
        out["collective-permute"] = 0
    for st in comp.stages:
        hlo = STAGE_HLO.get(st.primitive)
        if hlo is None:
            continue
        if st.primitive == "broadcast":
            if axis_sizes is None:
                raise CompositionError(
                    f"predicted_collectives: broadcast stage "
                    f"{st.signature()!r} lowers to tree_sends(n, radix) "
                    "collective-permutes — pass axis_sizes to size the "
                    "merged group"
                )
            n = 1
            for a in st.axes:
                n *= int(axis_sizes[a])
            out[hlo] += tree_sends(n, st.radix or DEFAULT_RADIX) * s_eff
        else:
            out[hlo] += s_eff
    return out


# ---------------------------------------------------------------------------
# Deriver: enumerate the legal compositions for an n-level mesh
# ---------------------------------------------------------------------------


def _contiguous_partitions(items: tuple) -> list[list[tuple]]:
    """All ordered partitions of ``items`` into contiguous groups."""
    if not items:
        return [[]]
    out = []
    for i in range(1, len(items) + 1):
        head = items[:i]
        for rest in _contiguous_partitions(items[i:]):
            out.append([head] + rest)
    return out


def derive_compositions(mesh_axes: Sequence[str]) -> tuple[Composition, ...]:
    """Enumerate the legal mean-allreduce compositions for a mesh.

    Recipe: reverse the axis tuple (fast level scatters first, slow
    level reduces innermost — the dcn-last ordering), partition it into
    contiguous LEVEL GROUPS (axis-merged variants: one collective per
    group over the merged axes), scatter every outer group, reduce the
    innermost group by either an ``allreduce`` or its own
    ``reduce_scatter``/``allgather`` pair, and conjugate-gather back
    out. ``2^k`` compositions for ``k`` axes — the menu's entries fall
    out as instances (``flat`` = the one-group allreduce,
    ``two_level`` = the ((fast), (rest)) split), and the rest are the
    pipelines the menu could not express (per-level ladders, merged
    scatters, scattered-slow-level variants). Every derived composition
    passes :func:`validate_composition` by construction (property-swept
    in the tests anyway).
    """
    names = tuple(mesh_axes)
    if not names:
        raise CompositionError("derive_compositions: empty mesh axis tuple")
    seen = set()
    out: list[Composition] = []
    for parts in _contiguous_partitions(names[::-1]):
        # each group back in mesh order for readable signatures
        groups = [tuple(sorted(g, key=names.index)) for g in parts]
        outer, inner = groups[:-1], groups[-1]
        for innermost in ("allreduce", "reduce_scatter"):
            stages = [Stage("reduce_scatter", g) for g in outer]
            stages.append(Stage(innermost, inner))
            if innermost == "reduce_scatter":
                stages.append(Stage("allgather", inner))
            stages.extend(Stage("allgather", g) for g in reversed(outer))
            comp = Composition(tuple(stages))
            sig = comp.signature()
            if sig not in seen:
                seen.add(sig)
                out.append(validate_composition(comp, names))
    return tuple(out)


def flat_composition(mesh_axes: Sequence[str]) -> Composition:
    """``flat`` as a derived instance: one fused allreduce over the
    merged axes."""
    return Composition((Stage("allreduce", tuple(mesh_axes)),))


def two_level_composition(mesh_axes: Sequence[str]) -> Composition:
    """``two_level`` as a derived instance: scatter the last (fast)
    axis, allreduce the shard over the rest, gather back — the
    reference's ``TwoDimensionalCommunicator`` pipeline
    (``two_dimensional_communicator.py`` (dagger)). On a flat mesh the
    rest is empty and this is the pinned rs->ag decomposition."""
    names = tuple(mesh_axes)
    fast, rest = (names[-1],), names[:-1]
    stages = [Stage("reduce_scatter", fast)]
    if rest:
        stages.append(Stage("allreduce", rest))
    stages.append(Stage("allgather", fast))
    return Composition(tuple(stages))


def zero_composition(mesh_axes: Sequence[str]) -> Composition:
    """``zero`` as a derived instance: the two_level reduction with the
    sharded update fused at the fully-reduced chunk —
    ``rs(all) > su > ag(all)`` on a flat mesh (arXiv:2004.13336),
    ``rs(fast) > ar(rest) > su > ag(fast)`` on a hierarchical one (the
    exact pipeline ``MultiNodeOptimizer._zero_update`` and the
    ParallelPlan zero group hand-wired before this layer existed)."""
    names = tuple(mesh_axes)
    fast, rest = (names[-1],), names[:-1]
    stages = [Stage("reduce_scatter", fast)]
    if rest:
        stages.append(Stage("allreduce", rest))
    stages.append(Stage("sharded_update"))
    stages.append(Stage("allgather", fast))
    return Composition(tuple(stages))


def broadcast_composition(
    mesh_axes: Sequence[str], radix: int = DEFAULT_RADIX
) -> Composition:
    """One multicast tree over the merged mesh axes (ISSUE 16): the
    root of the flattened group fans its buffer out in
    ``tree_depth(n, radix)`` ppermute rounds — the device-mesh
    rendering of the serving plane's one-to-many tree push. Spelled
    ``bc(a0+a1+a2)`` (``@r`` when the radix is non-default)."""
    r = int(radix)
    if r < 2:
        raise CompositionError(f"multicast radix must be >= 2, got {radix}")
    return Composition((Stage(
        "broadcast", tuple(mesh_axes),
        radix=(r if r != DEFAULT_RADIX else None),
    ),))


def compile_schedule(schedule, mesh_axes: Sequence[str]) -> Composition:
    """Lower a schedule spelling to a validated :class:`Composition`:
    a menu name (``'flat'``/``'two_level'``/``'zero'``), a signature
    string (actual axis names or canonical positional tokens), or a
    ``Composition`` instance. This is the ONE front door every executor
    call site uses — the menu entries are compiled, not special-cased.
    """
    names = tuple(mesh_axes)
    if isinstance(schedule, Composition):
        # compact_slices: an EXPANDED spelling (per-stage [sI:S]
        # addresses) validates but only the compact slices=S form is
        # executable — reconstitute it here, the one front door, so no
        # executor ever sees stage-addressed pipelines (review finding).
        return compact_slices(validate_composition(
            bind_composition(schedule, names), names))
    if schedule == "flat":
        return flat_composition(names)
    if schedule == "two_level":
        return two_level_composition(names)
    if schedule == "zero":
        return zero_composition(names)
    if isinstance(schedule, str) and (">" in schedule or "(" in schedule):
        comp = parse_signature(schedule)
        return compact_slices(validate_composition(
            bind_composition(comp, names), names))
    from chainermn_tpu.parallel.reduction_schedule import SCHEDULES

    raise CompositionError(
        f"unknown schedule {schedule!r}: expected one of {SCHEDULES}, a "
        "composition signature (e.g. 'rs(a1)>ar(a0)>ag(a1)'), or a "
        "Composition"
    )


def schedule_candidates(n_axes: int) -> tuple[str, ...]:
    """The ``reduction_schedule`` decision's candidate set for a
    ``n_axes``-level world shape: the legacy menu names first (cache
    back-compat — existing entries keep resolving, and the table default
    ``'flat'`` stays a member), then the DERIVED compositions the menu
    cannot express, keyed by canonical-token signature string. This is
    what makes the autotuner search generated schedules instead of a
    fixed menu."""
    from chainermn_tpu.parallel.reduction_schedule import SCHEDULES

    names = canonical_axis_names(max(1, int(n_axes)))
    menu_sigs = {flat_composition(names).signature(),
                 two_level_composition(names).signature()}
    derived = tuple(
        c.signature() for c in derive_compositions(names)
        if c.signature() not in menu_sigs
    )
    return tuple(SCHEDULES) + derived


def signature_for(schedule, n_axes: int) -> str:
    """Canonical-token signature for a winner string (menu name or
    signature) — the provenance spelling ``resolve_schedule`` reports,
    so a decision record names the actual pipeline, not just the menu
    label."""
    names = canonical_axis_names(max(1, int(n_axes)))
    return compile_schedule(schedule, names).signature()


# ---------------------------------------------------------------------------
# Executor: one staged interpreter for every composition
# ---------------------------------------------------------------------------


def _axes_arg(axes: tuple[str, ...]):
    return axes if len(axes) > 1 else axes[0]


def _replay_sizes(stages: Sequence[Stage], size: int, axis_sizes):
    """Static walk of the scatter frame: per-stage (size_in, size_out)
    element counts and the LIFO scatter stack — shared by the executor,
    the split ZeRO runners and the trace-time wire layout, so no two
    consumers can disagree about padding."""
    cur = int(size)
    stack: list[tuple[tuple[str, ...], int]] = []
    rows: list[tuple[Stage, int, int]] = []
    for st in stages:
        if st.primitive == "reduce_scatter":
            n = 1
            for a in st.axes:
                n *= int(axis_sizes[a])
            out = -(-cur // n)  # ceil: the padded shard length
            stack.append((st.axes, cur))
            rows.append((st, cur, out))
            cur = out
        elif st.primitive == "allgather":
            axes, orig = stack.pop()
            rows.append((st, cur, orig))
            cur = orig
        else:  # allreduce / sharded_update / broadcast: size unchanged
            rows.append((st, cur, cur))
    return rows, cur, stack


def stage_wire_layout(
    comp: Composition, axis_sizes: Mapping[str, int], itemsize: int,
    size: int,
) -> list[dict]:
    """Host-side per-stage wire table for one bucket of ``size``
    elements at ``itemsize`` wire bytes each: the payload bytes each
    collective stage carries (full buffer into a scatter / out of a
    gather, the reduced shard through an allreduce). This is what the
    trace ``wire`` events record per stage and what
    ``tools/trace_report.py``'s overlap section tabulates per
    composition signature.

    A SLICED composition (ISSUE 15) emits one row per stage PER SLICE,
    in the executor's skewed interleave order; each row additionally
    carries ``slice`` / ``n_slices`` (the effective, possibly degraded
    count) and that slice's own payload bytes — summed over slices the
    per-stage wire bytes equal the unsliced rendering's."""
    comp = compact_slices(comp)  # expanded spellings lay out compacted
    s_eff = effective_slices(comp.slices, size)
    if s_eff <= 1:
        rows, _, _ = _replay_sizes(comp.stages, size, axis_sizes)
        out = []
        for st, size_in, size_out in rows:
            hlo = STAGE_HLO.get(st.primitive)
            if hlo is None:
                continue
            nbytes = max(size_in, size_out) * itemsize
            row = {"stage": st.signature(), "op": hlo, "nbytes": nbytes}
            if st.primitive == "broadcast":
                n = 1
                for a in st.axes:
                    n *= int(axis_sizes[a])
                row["rounds"] = tree_depth(n, st.radix or DEFAULT_RADIX)
            out.append(row)
        return out
    bounds = slice_bounds(size, s_eff)
    # per-slice stage rows, keyed back to the BASE stage signature (the
    # spelling trace_report groups on); order = the skewed interleave.
    per_slice_rows = [
        {(st.signature(), j): (st, size_in, size_out)
         for j, (st, size_in, size_out) in enumerate(
             _replay_sizes(comp.stages, hi - lo, axis_sizes)[0])}
        for lo, hi in bounds
    ]
    out = []
    for st in expand_slices(comp, size):
        i, _ = st.slice
        base = dataclasses.replace(st, slice=None)
        j = comp.stages.index(base)
        hlo = STAGE_HLO.get(st.primitive)
        if hlo is None:
            continue
        _, size_in, size_out = per_slice_rows[i][(base.signature(), j)]
        row = {
            "stage": base.signature(), "op": hlo,
            "nbytes": max(size_in, size_out) * itemsize,
            "slice": i, "n_slices": s_eff,
        }
        if st.primitive == "broadcast":
            n = 1
            for a in st.axes:
                n *= int(axis_sizes[a])
            row["rounds"] = tree_depth(n, st.radix or DEFAULT_RADIX)
        out.append(row)
    return out


def reduce_composed(
    x,
    comp: Composition,
    *,
    op: str = "mean",
    update_fn: Optional[Callable] = None,
) -> Any:
    """Run ``comp`` on one buffer inside its named-axis context — THE
    executor every schedule lowers to. Stage semantics:

    - ``reduce_scatter``: ceil-pad the flat buffer into ``[n, c]`` rows
      over the stage's merged axis group and ``psum_scatter`` it (the
      shard is this member's exactly-summed 1/n slice);
    - ``allreduce``: ``psum`` over the group;
    - ``allgather``: conjugate gather of the matching scatter, un-pad;
    - ``sharded_update``: call ``update_fn`` on the fully-reduced
      shard (the ZeRO fuse point).

    The mean division lands immediately after the stage that completes
    the reduction over every mesh axis — exactly where
    ``decomposed_allreduce`` divides, so the menu schedules compile to
    byte-identical programs through this path. The single-stage
    ``ar(all)`` composition short-circuits to ``lax.pmean`` (the
    legacy ``flat`` program, literally).

    A SLICED composition (``comp.slices > 1``, ISSUE 15) cuts the flat
    buffer into ``effective_slices`` contiguous slices and issues the
    stages in the skewed interleave order (:func:`expand_slices`):
    the slices are data-independent, so slice i's slow stage and slice
    i+1's fast stage are concurrently schedulable — S× the per-stage
    collectives at 1/S payload, total wire bytes unchanged, and the
    concatenated result bitwise == the unsliced rendering on exact-
    dyadic inputs (each element still reduced over every axis exactly
    once).
    """
    from jax import lax

    from chainermn_tpu.parallel.collectives import (
        staged_allgather,
        staged_allreduce,
        staged_broadcast,
        staged_reduce_scatter,
    )

    if op not in ("sum", "mean"):
        raise ValueError(f"op must be 'sum' or 'mean', got {op!r}")
    comp = compact_slices(comp)  # expanded spellings run compacted
    stages = comp.stages
    if comp.has_update and update_fn is None:
        raise ValueError(
            f"composition {comp.signature()!r} has a sharded_update "
            "stage but no update_fn was given"
        )
    reduce_axes = tuple(
        a for s in stages
        if s.primitive in ("reduce_scatter", "allreduce") for a in s.axes
    )
    n_tot = 1
    for a in reduce_axes:
        n_tot *= lax.axis_size(a)
    # A broadcast-family pipeline reduces nothing: start the mean guard
    # already tripped so it never divides (n_tot is 1 anyway, but the
    # guard documents the invariant instead of relying on /1).
    rem_init = len(reduce_axes) if reduce_axes else -1

    s_eff = effective_slices(comp.slices, x.size)
    if s_eff > 1:
        if comp.has_update:
            raise CompositionError(
                f"{comp.signature()!r}: sliced execution with a "
                "sharded_update stage — the fuse point is unsliceable"
            )
        zigzag = comp.slice_layout == "zigzag"
        flat = x.reshape(-1)
        bounds = slice_bounds(flat.size, s_eff)
        # Per-slice pipeline state, stepped in the skewed interleave
        # order — each slice owns its scatter frame and divides once
        # when ITS reduction completes. The zigzag layout (ISSUE 16)
        # strides the cut — slice i = elements i, i+S, i+2S, ... — with
        # per-slice element counts identical to the contiguous bounds,
        # so only the indexing differs, never the wire.
        if zigzag:
            cur_s = [flat[i::s_eff] for i in range(s_eff)]
        else:
            cur_s = [flat[lo:hi] for lo, hi in bounds]
        stack_s: list[list[int]] = [[] for _ in range(s_eff)]
        rem_s = [rem_init] * s_eff
        for st in expand_slices(comp, flat.size):
            i, _ = st.slice
            if st.primitive == "reduce_scatter":
                stack_s[i].append(cur_s[i].size)
                cur_s[i] = staged_reduce_scatter(cur_s[i], st.axes)
                rem_s[i] -= len(st.axes)
            elif st.primitive == "allreduce":
                cur_s[i] = staged_allreduce(cur_s[i], st.axes)
                rem_s[i] -= len(st.axes)
            elif st.primitive == "broadcast":
                cur_s[i] = staged_broadcast(
                    cur_s[i], st.axes, radix=st.radix or DEFAULT_RADIX)
            else:  # allgather
                cur_s[i] = staged_allgather(
                    cur_s[i], st.axes, stack_s[i].pop())
            if rem_s[i] == 0 and op == "mean":
                cur_s[i] = cur_s[i] / n_tot
                rem_s[i] = -1  # divide exactly once per slice
        import jax.numpy as jnp

        if zigzag:
            out = jnp.zeros(flat.shape, cur_s[0].dtype)
            for i in range(s_eff):
                out = out.at[i::s_eff].set(cur_s[i])
            return out.reshape(x.shape)
        return jnp.concatenate(cur_s).reshape(x.shape)

    # flat short-circuit: one fused pmean, the pre-composition program.
    if (len(stages) == 1 and stages[0].primitive == "allreduce"
            and op == "mean"):
        return lax.pmean(x, _axes_arg(stages[0].axes))
    shape = x.shape
    cur = x.reshape(-1)
    stack: list[int] = []  # original sizes, LIFO with the scatters
    remaining = rem_init
    for st in stages:
        if st.primitive == "reduce_scatter":
            stack.append(cur.size)
            cur = staged_reduce_scatter(cur, st.axes)
            remaining -= len(st.axes)
        elif st.primitive == "allreduce":
            cur = staged_allreduce(cur, st.axes)
            remaining -= len(st.axes)
        elif st.primitive == "allgather":
            cur = staged_allgather(cur, st.axes, stack.pop())
        elif st.primitive == "broadcast":
            cur = staged_broadcast(
                cur, st.axes, radix=st.radix or DEFAULT_RADIX)
        else:  # sharded_update
            cur = update_fn(cur)
        if remaining == 0 and op == "mean":
            cur = cur / n_tot
            remaining = -1  # divide exactly once
    return cur.reshape(shape)


# -- split execution around the ZeRO fuse point -----------------------------


def run_reduce_prefix(
    g,
    stages: Sequence[Stage],
    *,
    total: int,
    wire_dtype=None,
):
    """Run a composition's reduce prefix (the stages before
    ``sharded_update``) on one leaf: flatten, optionally cast to the
    compressed wire dtype, scatter/reduce per stage, divide by
    ``total`` (the full data-parallel degree) and return the mean chunk
    in the leaf's dtype — exactly the hand-wired
    ``zero_grad_scatter``/``MultiNodeOptimizer._zero_update`` scatter
    arithmetic, now derived from the composition."""
    import jax.numpy as jnp

    from chainermn_tpu.parallel.collectives import (
        staged_allreduce,
        staged_reduce_scatter,
    )

    cur = g.reshape(-1)
    if wire_dtype is not None and jnp.issubdtype(g.dtype, jnp.floating):
        cur = cur.astype(wire_dtype)
    for st in stages:
        if st.primitive == "reduce_scatter":
            cur = staged_reduce_scatter(cur, st.axes)
        elif st.primitive == "allreduce":
            cur = staged_allreduce(cur, st.axes)
        else:
            raise CompositionError(
                f"{st.signature()}: only reduce stages run before the "
                "sharded_update"
            )
    return (cur / total).astype(g.dtype)


def run_gather_suffix(
    u_chunk,
    like,
    stages: Sequence[Stage],
    prefix: Sequence[Stage],
):
    """Run a composition's gather suffix (the stages after
    ``sharded_update``) on one updated chunk, reassembling ``like``'s
    shape/dtype. The un-pad sizes replay the prefix's static scatter
    frame (:func:`_replay_sizes`), so prefix and suffix can never
    disagree about the padding."""
    from jax import lax

    from chainermn_tpu.parallel.collectives import staged_allgather

    axis_sizes = {}
    for st in tuple(prefix) + tuple(stages):
        for a in st.axes:
            if a not in axis_sizes:
                axis_sizes[a] = lax.axis_size(a)
    _, _, stack = _replay_sizes(prefix, like.size, axis_sizes)
    cur = u_chunk
    for st in stages:
        if st.primitive != "allgather":
            raise CompositionError(
                f"{st.signature()}: only allgather stages run after the "
                "sharded_update"
            )
        _, orig = stack.pop()
        cur = staged_allgather(cur, st.axes, orig)
    return cur.reshape(like.shape).astype(like.dtype)


def reduce_composed_tree(leaves: list, comp: Composition, *, op="mean"):
    """Reduce a LIST of leaves under ``comp``. The single-stage
    ``ar(all)`` composition keeps the hand-wired list form (one fused
    ``pmean`` over all leaves — ONE HLO all-reduce, the ParallelPlan's
    pre-composition program, byte-identical); every other composition
    pipelines each leaf's flat buffer through the executor (per-leaf
    stage collectives — the documented cost of a scattered pipeline
    without a packing layer, pinned in tests/test_composition.py)."""
    from jax import lax

    comp = compact_slices(comp)  # expanded spellings run compacted
    stages = comp.stages
    if (len(stages) == 1 and stages[0].primitive == "allreduce"
            and op == "mean" and comp.slices == 1):
        return lax.pmean(leaves, _axes_arg(stages[0].axes))
    return [reduce_composed(g, comp, op=op) for g in leaves]


__all__ = [
    "Composition",
    "CompositionError",
    "DEFAULT_RADIX",
    "PRIMITIVES",
    "STAGE_HLO",
    "Stage",
    "bind_composition",
    "broadcast_composition",
    "canonical_axis_names",
    "compact_slices",
    "compile_schedule",
    "derive_compositions",
    "effective_slices",
    "expand_slices",
    "flat_composition",
    "parse_signature",
    "predicted_collectives",
    "reduce_composed",
    "reduce_composed_tree",
    "run_gather_suffix",
    "run_reduce_prefix",
    "schedule_candidates",
    "signature_for",
    "slice_bounds",
    "sliced_composition",
    "stage_wire_layout",
    "tree_depth",
    "tree_sends",
    "two_level_composition",
    "validate_composition",
    "zero_composition",
]
