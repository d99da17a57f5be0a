"""The plain reference of the chain ``samtools view`` predicate ->
coordinate sort -> duplicate marking, record by record.

Straightforward Python over parsed records (anything with ``flag``,
``refid``, ``pos``, ``mapq``, ``cigar`` as ``[(length, op letter)]`` and
``qual`` as bytes or None: ``bam_oracle.ORecord`` is one).  Imports
nothing of the program; ``benchmark/reference_chain.py`` is its numpy
copy over the generator's arrays and is held to it by a test.

The marking rule is the program's (``ops/markdup.py``), written out:

- a record is *examined* unless it is unmapped, secondary or
  supplementary (``0x904``) or has no reference; the others are never
  marked and never make another record a duplicate;
- its key is (reference, unclipped 5' position, orientation): the
  position less the clipped bases that lead the CIGAR for a forward
  read, the last aligned base plus the clipped bases that trail it for
  a reverse read (an alignment with no reference-consuming op spans one
  base);
- its score is the sum of its base qualities that are >= 15 (the 0xFF
  of "no qualities" counts nothing);
- of the examined records that share a key the best score stays, ties
  to the one that comes first in coordinate order, and every other one
  gets ``0x400``; a ``0x400`` the input carried is kept either way.

Where this departs from Picard ``MarkDuplicates`` / GATK
``MarkDuplicatesSpark``: the key is the *fragment's* (a read's own 5'
end), not the pair's (both mates' ends and the library); the score is
the one read's, not the pair's; there are no optical duplicates and no
libraries.  ``samtools markdup -m s`` on single ends is the nearer kin.
"""

MARKDUP_EXCLUDE = 0x4 | 0x100 | 0x800
DUPLICATE = 0x400
CLIPS = "SH"
CONSUMES_REFERENCE = "MDN=X"
MIN_QUALITY = 15
NO_QUALITY = 0xFF


def parse_view(spec):
    """``samtools view``'s ``-f INT -F INT -q INT`` as a dict."""
    want = {"-f": 0, "-F": 0, "-q": 0}
    words = spec.split()
    if len(words) % 2:
        raise ValueError(f"an option of {spec!r} lacks its value")
    for opt, value in zip(words[0::2], words[1::2]):
        if opt not in want:
            raise ValueError(f"the reference knows no option {opt!r}")
        want[opt] = int(value, 0)
    return want


def view_keeps(rec, want):
    """Whether ``samtools view`` with these options passes the record."""
    return ((rec.flag & want["-f"]) == want["-f"]
            and (rec.flag & want["-F"]) == 0
            and rec.mapq >= want["-q"])


def coordinate_order(records):
    """Indices in SAM coordinate order: by reference with the records
    that have none last, then by position; equal keys keep input order
    (``sorted`` is stable)."""
    def key(i):
        rec = records[i]
        return (rec.refid if rec.refid >= 0 else float("inf"), rec.pos)
    return sorted(range(len(records)), key=key)


def examined(rec):
    return (rec.flag & MARKDUP_EXCLUDE) == 0 and rec.refid >= 0


def duplicate_key(rec):
    """(reference, unclipped 5' position, is reverse)."""
    cigar = list(rec.cigar)
    reverse = bool(rec.flag & 0x10)
    if not reverse:
        lead = 0
        for length, op in cigar:
            if op not in CLIPS:
                break
            lead += length
        return rec.refid, rec.pos - lead, False
    trail = 0
    for length, op in reversed(cigar):
        if op not in CLIPS:
            break
        trail += length
    span = sum(length for length, op in cigar if op in CONSUMES_REFERENCE)
    return rec.refid, rec.pos + max(span, 1) - 1 + trail, True


def score(rec):
    return sum(q for q in (rec.qual or b"")
               if q >= MIN_QUALITY and q != NO_QUALITY)


def mark_duplicates(records):
    """``(is_duplicate, examined count)`` for records in coordinate
    order: one walk, the best record of each key so far remembered; a
    later one takes its place only with a strictly better score."""
    best = {}
    marked = [False] * len(records)
    n_examined = 0
    for i, rec in enumerate(records):
        if not examined(rec):
            continue
        n_examined += 1
        key = duplicate_key(rec)
        if key not in best:
            best[key] = i
        elif score(rec) > score(records[best[key]]):
            marked[best[key]] = True
            best[key] = i
        else:
            marked[i] = True
    return marked, n_examined


def chain(records, view_spec):
    """The whole chain on input-order records.  Returns a dict:
    ``kept`` (indices into ``records`` in output order), ``flags`` (the
    output records' flags, ``0x400`` OR-ed into the losers), ``marked``
    (parallel booleans), ``examined``, ``duplicates``."""
    want = parse_view(view_spec)
    passed = [i for i, rec in enumerate(records) if view_keeps(rec, want)]
    ordered = [passed[j] for j in
               coordinate_order([records[i] for i in passed])]
    marked, n_examined = mark_duplicates([records[i] for i in ordered])
    flags = [records[i].flag | (DUPLICATE if m else 0)
             for i, m in zip(ordered, marked)]
    return {"kept": ordered, "flags": flags, "marked": marked,
            "examined": n_examined, "duplicates": sum(marked)}
