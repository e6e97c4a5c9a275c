"""The plain reference of ``group`` (fgumi's GroupReadsByUmi, ``--strategy
adjacency --edits 1``): what the command must write for an ``amplicon_bam``
input, worked out from the generator's arrays in straightforward numpy.
Nothing of the program is imported and nothing it made is read.

The semantics, as fgumi's ``src/lib/commands/group.rs`` and
``crates/fgumi-umi/src/assigner.rs`` describe them:

1. **Position groups.** Consecutive templates with one template-coordinate
   key form a position group, in stream order: the library and the two ends'
   (contig, unclipped 5' position, strand), the lower end first.
2. **Filter.** A template whose UMI holds an ``N`` is dropped and counted
   (``ns_in_umi``); a position group with no template left is skipped.
3. **Orientation sub-groups.** A position group's templates are split by
   (R1 on the forward strand, R2 on the forward strand) and the sub-groups
   are assigned in ascending order of that pair, ``False < True``.
4. **Directed adjacency** a sub-group (``reference.adjacency_molecules``:
   unique UMIs ranked by (-count, string), roots in rank order capture,
   breadth first, every unassigned UMI within one mismatch whose count is at
   most ``count // 2 + 1``).
5. **Molecule ids** are minted in root order from one counter that runs on
   over sub-groups and position groups in stream order.
6. **Output.** Every kept template's records in the input's order, R1 then
   R2, byte for byte the input records with ``MI:Z:<id>`` appended; the
   input's header.

Departures from upstream's description, each by what the input is:
the layout makes one library, so the key is the two ends; every read is
mapped with MAPQ 60, passes the vendor filter and has no ``MQ`` tag, so of
the filters only the UMI's ``N`` is modelled; a UMI is ``ACGT`` or ``N``
(other invalid strings, which upstream gives an id of their own, are not
made); ``--min-umi-length`` is unset, so no UMI is truncated; the output is
not re-sorted by molecule (fgbio's GroupReadsByUmi writes a position group
sorted by ``MI``; fgumi's ``group`` and this program keep the stream's
order); ``reference.adjacency_molecules`` knows one mismatch, which is the
configuration's.

**The control.** The deployment computes no float (``precision``: exact
integers), so there is no precision below it. ``benchmark/control.py`` asks
for one by passing ``np.float32``: that is answered by the same reference at
**zero mismatches** (identity grouping: every distinct UMI a molecule of its
own, ids in rank order), the nearest rule below the configuration's. It has
to come out as not correct.
"""

import numpy as np

import reference
import traffic

N_CODE = traffic.N_CODE


def identity_molecules(umi_ints):
    """The control's rule, zero mismatches: every unique UMI is a root, ids
    in rank order (-count, string). Returns each template's id."""
    uniq, inverse, counts = np.unique(umi_ints, return_inverse=True,
                                      return_counts=True)
    rank = np.empty(len(uniq), dtype=np.int64)
    rank[np.lexsort((uniq, -counts))] = np.arange(len(uniq))
    return rank[inverse]


def assign(keys, orient, umi, edits=1):
    """Molecule ids of a stream of templates. ``keys`` (n, k): each
    template's position key; ``orient`` (n,): its orientation sub-group's
    rank (2 * R1 forward + R2 forward); ``umi`` (n, L): its UMI's codes
    (0-3 ``ACGT``, 4 ``N``). Returns (kept mask, the kept templates' ids,
    what was counted)."""
    keys = np.asarray(keys).reshape(len(umi), -1)
    orient, umi = np.asarray(orient), np.asarray(umi)
    n, ulen = umi.shape
    kept = ~(umi == N_CODE).any(axis=1)
    new_group = np.concatenate(([True], (keys[1:] != keys[:-1]).any(axis=1)))
    group_of = np.cumsum(new_group) - 1
    ints = (np.where(kept[:, None], umi, 0).astype(np.int64)
            * 4 ** np.arange(ulen - 1, -1, -1, dtype=np.int64)).sum(axis=1)
    molecules = (reference.adjacency_molecules if edits
                 else lambda umi_ints, _ulen: identity_molecules(umi_ints))
    ids = np.full(n, -1, dtype=np.int64)
    counted = dict(position_groups=0, subgroups=0, graphs=0, unique_umis=0,
                   templates=int(kept.sum()), ns_in_umi=int((~kept).sum()),
                   molecules=0)
    rows = np.flatnonzero(kept)
    # stream order of position groups, then ascending orientation
    order = rows[np.lexsort((rows, orient[rows], group_of[rows]))]
    sub = np.stack([group_of[order], orient[order]], axis=1)
    starts = np.flatnonzero(np.concatenate(
        ([True], (sub[1:] != sub[:-1]).any(axis=1))))
    counted["position_groups"] = len(np.unique(group_of[rows]))
    for lo, hi in zip(starts, np.append(starts[1:], len(order))):
        idx = order[lo:hi]
        local = molecules(ints[idx], ulen)
        uniques = len(np.unique(ints[idx]))
        ids[idx] = counted["molecules"] + local
        counted["molecules"] += int(local.max()) + 1
        counted["subgroups"] += 1
        counted["unique_umis"] += uniques
        counted["graphs"] += uniques > 1
    return kept, ids[kept], counted


def template_keys(d):
    """(position key, orientation rank) of every template of an
    ``amplicon_bam`` input: the forward read's unclipped 5' end is its first
    base, the reverse-flagged read's its last; one contig, one library."""
    loc = d["locus"][d["fam"]]
    low = d["start"][loc]
    high = low + d["insert"][loc] - 1
    r1_forward = ~d["r1_reverse"]
    return (np.stack([low, high], axis=1),
            2 * r1_forward.astype(np.int64) + (~r1_forward).astype(np.int64))


def group(d, edits=1):
    """Expected output of ``group --strategy adjacency`` on the arrays of an
    ``amplicon_bam`` input. Returns (flat record bytes, records, what was
    counted)."""
    layout = traffic.kind_module("amplicon_bam")
    keys, orient = template_keys(d)
    kept, ids, counted = assign(keys, orient, d["umi_t"], edits)
    rows = np.flatnonzero(kept)
    chunks = []
    for lo in range(0, len(rows), 65536):
        idx = rows[lo:lo + 65536]
        mi_dig, mi_n = traffic.digits(ids[lo:lo + 65536], 10)
        tail = [(traffic.const(len(idx), b"MIZ"), None), (mi_dig, mi_n),
                (traffic.const(len(idx), b"\x00"), None)]
        # one row per template: R1's record, then R2's
        flat, _ = traffic.pack_rows(layout.records(idx, d, 0, tail)
                                    + layout.records(idx, d, 1, tail))
        chunks.append(flat)
    flat = np.concatenate(chunks) if chunks else np.empty(0, np.uint8)
    return flat, 2 * len(rows), counted
