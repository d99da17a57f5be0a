"""CRAM CORE-block bit codecs + rANS order-1 encode.

Foreign htsjdk/samtools CRAMs route data series through CORE-block bit
codecs — canonical Huffman, BETA, GAMMA, SUBEXP — which the reader now
decodes. Spec-exact worked examples pin the bit-level formats; the
core-profile writer (CF→Huffman, MQ→BETA, FN→GAMMA) gives true
round-trip coverage through the whole container path. The rANS order-1
encoder is verified against BOTH the independent Python decoder and
the native C decoder.
"""

import struct

import numpy as np
import pytest

from disq_tpu.cram.codec import (
    BitCursor,
    BitWriter,
    _gamma_read,
    _gamma_write,
    _subexp_read,
    _subexp_write,
    canonical_assign,
    huffman_code_lengths,
)
from disq_tpu.cram.rans import _decode1, rans_decode, rans_encode_order1


class TestBitCodecsWorkedExamples:
    """Hand-computed bit patterns per the CRAM 3.0 codec definitions."""

    def test_beta_bits(self):
        # BETA(offset=0, nbits=4): 5 -> 0101; 12 -> 1100
        bw = BitWriter()
        bw.write(5, 4)
        bw.write(12, 4)
        assert bw.flush() == bytes([0b0101_1100])

    def test_gamma_worked_example(self):
        # Elias gamma of v=5 (offset 0): 2 zeros + '101' -> 00101
        bw = BitWriter()
        _gamma_write(bw, 5, 0)
        data = bw.flush()
        assert data == bytes([0b00101_000])
        assert _gamma_read(BitCursor(data), 0) == 5

    def test_gamma_offset_allows_zero(self):
        bw = BitWriter()
        _gamma_write(bw, 0, 1)  # v = 1 -> single '1' bit
        data = bw.flush()
        assert data == bytes([0b1000_0000])
        assert _gamma_read(BitCursor(data), 1) == 0

    def test_subexp_worked_example(self):
        # SUBEXP(offset=0, k=2), value 5: b=2, u=1 -> '1','0', then
        # b=k+u-1=2 low bits of 5 (0b101 minus implicit top) = '01'
        bw = BitWriter()
        _subexp_write(bw, 5, 0, 2)
        data = bw.flush()
        assert data == bytes([0b1001_0000])
        assert _subexp_read(BitCursor(data), 0, 2) == 5

    def test_subexp_small_value(self):
        # value 2 < 2^k: '0' then 2 in k=2 bits -> 010
        bw = BitWriter()
        _subexp_write(bw, 2, 0, 2)
        data = bw.flush()
        assert data == bytes([0b0100_0000])
        assert _subexp_read(BitCursor(data), 0, 2) == 2

    @pytest.mark.parametrize("codec", ["beta", "gamma", "subexp"])
    def test_round_trip_sweep(self, codec):
        rng = np.random.default_rng(1)
        vals = rng.integers(0, 1 << 16, 500).tolist()
        bw = BitWriter()
        for v in vals:
            if codec == "beta":
                bw.write(v, 17)
            elif codec == "gamma":
                _gamma_write(bw, v, 1)
            else:
                _subexp_write(bw, v, 0, 3)
        bc = BitCursor(bw.flush())
        for v in vals:
            if codec == "beta":
                assert bc.bits(17) == v
            elif codec == "gamma":
                assert _gamma_read(bc, 1) == v
            else:
                assert _subexp_read(bc, 0, 3) == v

    def test_canonical_huffman_assignment(self):
        # lengths {A:1, B:2, C:2} with values A=0,B=1,C=2 ->
        # canonical codes: 0, 10, 11
        codes = canonical_assign([0, 1, 2], [1, 2, 2])
        assert codes == {0: (0b0, 1), 1: (0b10, 2), 2: (0b11, 2)}

    def test_huffman_lengths_kraft(self):
        freqs = {i: f for i, f in enumerate([50, 20, 15, 10, 5])}
        lens = huffman_code_lengths(freqs)
        assert sum(2.0 ** -l for l in lens.values()) <= 1.0 + 1e-9
        assert lens[0] <= lens[4]


class TestCoreProfileRoundTrip:
    """CF/MQ/FN through CORE bit codecs, end-to-end through the
    container writer and back through the reader."""

    def _batch(self, n=300, seed=3):
        from tests.bam_oracle import synth_records
        from tests.test_bam_codec import _blob
        from disq_tpu.bam import decode_records

        return decode_records(_blob(synth_records(n, seed=seed)))

    def test_container_round_trip(self):
        from disq_tpu.cram.codec import (
            decode_container_records, encode_container,
        )
        from disq_tpu.cram.structure import ContainerHeader
        from disq_tpu.cram.io import Cursor

        batch = self._batch()
        one = batch.take(np.flatnonzero(np.asarray(batch.refid) == 0))
        blob, _info = encode_container(one, 0, 0, core_profile=True)
        cur = Cursor(blob)
        ContainerHeader.read(cur)  # skip the container header
        back = decode_container_records(bytes(blob[cur.off:]))
        for col in ("refid", "pos", "mapq", "flag", "names", "seqs",
                    "quals", "cigars", "tags"):
            np.testing.assert_array_equal(
                getattr(back, col), getattr(one, col), err_msg=col)

    def test_storage_round_trip_with_core_flag(self, tmp_path, monkeypatch):
        from disq_tpu.api import ReadsStorage
        from tests.bam_oracle import DEFAULT_REFS, make_bam_bytes, synth_records

        src = tmp_path / "in.bam"
        src.write_bytes(
            make_bam_bytes(DEFAULT_REFS,
                           synth_records(400, seed=5, sorted_coord=True)))
        ds = ReadsStorage.make_default().read(str(src))
        out = tmp_path / "o.cram"
        monkeypatch.setenv("DISQ_TPU_CRAM_CORE", "1")
        ReadsStorage.make_default().write(ds, str(out))
        monkeypatch.delenv("DISQ_TPU_CRAM_CORE")
        back = ReadsStorage.make_default().read(str(out))
        assert back.count() == 400
        np.testing.assert_array_equal(back.reads.mapq, ds.reads.mapq)
        np.testing.assert_array_equal(back.reads.flag, ds.reads.flag)
        np.testing.assert_array_equal(back.reads.seqs, ds.reads.seqs)
        np.testing.assert_array_equal(back.reads.quals, ds.reads.quals)


class TestRansOrder1:
    CASES = None

    def _cases(self):
        rng = np.random.default_rng(0)
        return [
            b"", b"a", b"ab", b"abc", b"abcd",
            bytes(rng.integers(30, 45, 5000, dtype=np.uint8)),
            np.repeat(rng.integers(30, 45, 500, dtype=np.uint8),
                      17).tobytes(),
            bytes(rng.integers(0, 256, 3000, dtype=np.uint8)),
            b"ACGT" * 2000,
        ]

    def test_round_trip_python_decoder(self):
        for raw in self._cases():
            enc = rans_encode_order1(raw)
            order, csize, rsize = struct.unpack_from("<BII", enc, 0)
            assert order == 1
            got = _decode1(memoryview(enc)[9:9 + csize], rsize) if rsize \
                else b""
            assert got == raw

    def test_round_trip_native_decoder(self):
        try:
            from disq_tpu.native import rans_decode_native
        except ImportError:
            pytest.skip("native codec not built")
        for raw in self._cases():
            if raw:
                assert rans_decode_native(rans_encode_order1(raw)) == raw

    def test_order1_beats_order0_on_qualities(self):
        from disq_tpu.cram.rans import rans_encode_order0

        rng = np.random.default_rng(7)
        # markov-ish quality track: strong prev-byte correlation
        steps = rng.integers(-2, 3, 20000)
        quals = np.clip(33 + np.cumsum(steps) % 8, 33, 41).astype(np.uint8)
        raw = quals.tobytes()
        assert len(rans_encode_order1(raw)) < len(rans_encode_order0(raw))

    def test_storage_round_trip_order1_flag(self, tmp_path, monkeypatch):
        from disq_tpu.api import ReadsStorage
        from tests.bam_oracle import DEFAULT_REFS, make_bam_bytes, synth_records

        src = tmp_path / "i.bam"
        src.write_bytes(make_bam_bytes(
            DEFAULT_REFS, synth_records(150, seed=12, sorted_coord=True)))
        ds = ReadsStorage.make_default().read(str(src))
        out = tmp_path / "o1.cram"
        monkeypatch.setenv("DISQ_TPU_CRAM_RANS_O1", "1")
        ReadsStorage.make_default().write(ds, str(out))
        monkeypatch.delenv("DISQ_TPU_CRAM_RANS_O1")
        back = ReadsStorage.make_default().read(str(out))
        np.testing.assert_array_equal(back.reads.quals, ds.reads.quals)

    def test_qs_blocks_written_order1(self, tmp_path):
        from disq_tpu.cram.codec import CID, encode_container
        from disq_tpu.cram.structure import Block, EXTERNAL
        from disq_tpu.cram.io import Cursor

        batch = TestCoreProfileRoundTrip()._batch(100, seed=9)
        one = batch.take(np.flatnonzero(np.asarray(batch.refid) == 0))
        blob, _ = encode_container(one, 0, 0)
        from disq_tpu.cram.structure import ContainerHeader

        cur = Cursor(blob)
        ContainerHeader.read(cur)  # skip the container header
        found = None
        while cur.off < len(blob):
            b = Block.read(cur)
            if b.content_type == EXTERNAL and b.content_id == CID["QS"]:
                found = b
        assert found is not None and len(found.data) > 0

class TestForeignSliceShapes:
    """Hand-built slices in shapes OUR writer never emits but foreign
    htsjdk/samtools writers do: multi-reference (refid -2, per-record
    RI series) and AP-delta coding."""

    def _build_slice(self, recs, ap_delta):
        """recs: list of (refid, pos0, name, seq_bytes). Returns the
        container *block section* bytes (compression header + slice)."""
        from disq_tpu.cram.codec import (
            CF_DETACHED, CF_QS_STORED, CID, CompressionHeader, _Streams,
        )
        from disq_tpu.cram.structure import (
            Block, COMPRESSION_HEADER, CORE, EXTERNAL, MAPPED_SLICE, RAW,
            SliceHeader,
        )

        streams = _Streams()
        prev_ap = 0  # slice ref_start seed
        for refid, pos0, name, seq in recs:
            streams.put_itf8(CID["BF"], 0)
            streams.put_itf8(CID["CF"], CF_QS_STORED | CF_DETACHED)
            streams.put_itf8(CID["RL"], len(seq))
            streams.put_itf8(CID["RI"], refid)
            ap = pos0 + 1
            if ap_delta:
                streams.put_itf8(CID["AP"], ap - prev_ap)
                prev_ap = ap
            else:
                streams.put_itf8(CID["AP"], ap)
            streams.put_itf8(CID["RG"], -1)
            streams.put_bytes(CID["RN"], name + b"\x00")
            streams.put_itf8(CID["MF"], 0)
            streams.put_itf8(CID["NS"], -1)
            streams.put_itf8(CID["NP"], 0)
            streams.put_itf8(CID["TS"], 0)
            streams.put_itf8(CID["TL"], 0)
            # one verbatim-bases feature covering the whole read
            streams.put_itf8(CID["FN"], 1)
            streams.put_bytes(CID["FC"], b"b")
            streams.put_itf8(CID["FP"], 1)
            streams.put_itf8(CID["BB_LEN"], len(seq))
            streams.put_bytes(CID["BB_VAL"], seq)
            streams.put_itf8(CID["MQ"], 37)
            streams.put_bytes(CID["QS"], b"#" * len(seq))
        from disq_tpu.cram.codec import _enc_external

        comp = CompressionHeader(
            rn_preserved=True, ap_delta=ap_delta, ref_required=False,
            tag_lines=[[]],
        )
        comp.enc_overrides["RI"] = _enc_external(CID["RI"])
        ch = Block(COMPRESSION_HEADER, 0, comp.to_bytes(), RAW)
        ext = [Block(EXTERNAL, cid, bytes(streams.data[cid]), RAW)
               for cid in sorted(streams.data)]
        sh = SliceHeader(
            ref_seq_id=-2, ref_start=0, ref_span=0, n_records=len(recs),
            record_counter=0, n_blocks=1 + len(ext),
            content_ids=[b.content_id for b in ext],
        )
        return (
            ch.to_bytes()
            + Block(MAPPED_SLICE, 0, sh.to_bytes(), RAW).to_bytes()
            + Block(CORE, 0, b"", RAW).to_bytes()
            + b"".join(b.to_bytes() for b in ext)
        )

    @pytest.mark.parametrize("ap_delta", [False, True])
    def test_multiref_slice_decodes(self, ap_delta):
        from disq_tpu.cram.codec import decode_container_records

        recs = [
            (2, 100, b"r1", b"ACGT"),
            (0, 7, b"r2", b"GGGA"),
            (5, 250, b"r3", b"TTTTT"),
            (0, 9, b"r4", b"CA"),
        ]
        batch = decode_container_records(self._build_slice(recs, ap_delta))
        assert batch.count == 4
        np.testing.assert_array_equal(batch.refid, [2, 0, 5, 0])
        np.testing.assert_array_equal(batch.pos, [100, 7, 250, 9])
        from disq_tpu.bam.columnar import SEQ_NT16

        got0 = "".join(SEQ_NT16[v] for v in
                       batch.seqs[batch.seq_offsets[0]:batch.seq_offsets[1]])
        assert got0 == "ACGT"

    def test_multiref_reference_tail_uses_record_refid(self):
        # FN=0 mapped record: the whole read is a reference-matching
        # tail, fetched with the PER-RECORD refid, not the slice's -2
        from disq_tpu.cram.codec import decode_container_records
        from disq_tpu.bam.columnar import SEQ_NT16

        recs = [(3, 10, b"t1", b"")]  # seq comes from the reference

        # build by hand with RL=4 but zero features
        from disq_tpu.cram.codec import (
            CF_DETACHED, CF_QS_STORED, CID, CompressionHeader, _Streams,
        )
        from disq_tpu.cram.structure import (
            Block, COMPRESSION_HEADER, CORE, EXTERNAL, MAPPED_SLICE, RAW,
            SliceHeader,
        )

        streams = _Streams()
        streams.put_itf8(CID["BF"], 0)
        streams.put_itf8(CID["CF"], CF_QS_STORED | CF_DETACHED)
        streams.put_itf8(CID["RL"], 4)
        streams.put_itf8(CID["RI"], 3)
        streams.put_itf8(CID["AP"], 11)
        streams.put_itf8(CID["RG"], -1)
        streams.put_bytes(CID["RN"], b"t1\x00")
        streams.put_itf8(CID["MF"], 0)
        streams.put_itf8(CID["NS"], -1)
        streams.put_itf8(CID["NP"], 0)
        streams.put_itf8(CID["TS"], 0)
        streams.put_itf8(CID["TL"], 0)
        streams.put_itf8(CID["FN"], 0)
        streams.put_itf8(CID["MQ"], 11)
        streams.put_bytes(CID["QS"], b"####")
        from disq_tpu.cram.codec import _enc_external

        comp = CompressionHeader(rn_preserved=True, ap_delta=False,
                                 ref_required=True, tag_lines=[[]])
        comp.enc_overrides["RI"] = _enc_external(CID["RI"])
        ch = Block(COMPRESSION_HEADER, 0, comp.to_bytes(), RAW)
        ext = [Block(EXTERNAL, cid, bytes(streams.data[cid]), RAW)
               for cid in sorted(streams.data)]
        sh = SliceHeader(ref_seq_id=-2, ref_start=0, ref_span=0,
                         n_records=1, record_counter=0,
                         n_blocks=1 + len(ext),
                         content_ids=[b.content_id for b in ext])
        blob = (ch.to_bytes()
                + Block(MAPPED_SLICE, 0, sh.to_bytes(), RAW).to_bytes()
                + Block(CORE, 0, b"", RAW).to_bytes()
                + b"".join(b.to_bytes() for b in ext))

        fetched = []

        def ref_fetch(refid, start0, length):
            fetched.append((refid, start0, length))
            return b"GATC"[:length]

        batch = decode_container_records(blob, ref_fetch)
        assert fetched == [(3, 10, 4)]
        got = "".join(SEQ_NT16[v] for v in batch.seqs[:4])
        assert got == "GATC"

    def test_written_headers_do_not_declare_ri(self):
        # our writer is single-ref: a dangling RI declaration (no
        # backing block) would break strict foreign readers
        from disq_tpu.cram.codec import CompressionHeader

        hdr = CompressionHeader(tag_lines=[[]])
        parsed = CompressionHeader.parse(hdr.to_bytes())
        assert "RI" not in parsed.series_enc
        assert "BF" in parsed.series_enc

    def test_multiref_without_ri_series_rejected(self):
        from disq_tpu.cram.codec import decode_container_records

        blob = self._build_slice([(1, 5, b"x", b"AC")], False)
        # strip the RI declaration by re-parsing and forging a header
        # without it is intricate; instead assert the error message path
        # via a header whose parse drops RI
        import disq_tpu.cram.codec as codec

        orig = codec.CompressionHeader.parse

        def parse_no_ri(data):
            out = orig(data)
            out.series_enc.pop("RI", None)
            return out

        codec.CompressionHeader.parse = parse_no_ri
        try:
            with pytest.raises(ValueError, match="RI series"):
                decode_container_records(blob)
        finally:
            codec.CompressionHeader.parse = orig


class TestSharedBlockLayouts:
    """Foreign CRAMs may route several data series through ONE external
    block (values interleaved in record order). The bulk fast paths
    must decline such layouts and the per-record loop must decode them
    correctly."""

    def _shared_slice(self):
        from disq_tpu.cram.codec import (
            CID,
            CompressionHeader,
            E_EXTERNAL,
            Encoding,
            _decode_slice,
        )
        from disq_tpu.cram.io import write_itf8
        from disq_tpu.cram.structure import SliceHeader

        ext = lambda cid: Encoding(E_EXTERNAL, cid)  # noqa: E731
        SHARED = 99
        comp = CompressionHeader(
            rn_preserved=False, ap_delta=False, ref_required=False,
            tag_lines=[[]],
            series_enc={
                # BF and CF share one block — interleaved per record
                "BF": ext(SHARED), "CF": ext(SHARED),
                "RL": ext(CID["RL"]), "AP": ext(CID["AP"]),
                "RG": ext(CID["RG"]), "MF": ext(CID["MF"]),
                "NS": ext(CID["NS"]), "NP": ext(CID["NP"]),
                "TS": ext(CID["TS"]), "TL": ext(CID["TL"]),
                "FN": ext(CID["FN"]), "MQ": ext(CID["MQ"]),
                "QS": ext(CID["QS"]),
            },
        )
        n = 3
        flags = [0, 16, 4]
        cf = 0x1 | 0x2 | 0x8   # QS stored, detached, unknown bases
        rl = [4, 5, 3]
        blocks = {
            SHARED: b"".join(
                write_itf8(f) + write_itf8(cf) for f in flags),
            CID["RL"]: b"".join(write_itf8(v) for v in rl),
            CID["AP"]: b"".join(write_itf8(v) for v in (11, 21, 0)),
            CID["RG"]: write_itf8(-1) * n,
            CID["MF"]: write_itf8(0) * n,
            CID["NS"]: b"".join(write_itf8(v) for v in (-1, -1, -1)),
            CID["NP"]: write_itf8(0) * n,
            CID["TS"]: write_itf8(0) * n,
            CID["TL"]: write_itf8(0) * n,
            CID["FN"]: write_itf8(0) * n,
            CID["MQ"]: b"".join(write_itf8(v) for v in (9, 8, 0)),
            CID["QS"]: bytes(range(sum(rl))),
        }
        hdr = SliceHeader(
            ref_seq_id=0, ref_start=11, ref_span=20, n_records=n,
            record_counter=0, n_blocks=len(blocks),
            content_ids=sorted(blocks),
        )
        return _decode_slice, hdr, comp, blocks, flags, rl

    def test_interleaved_shared_block_decodes_via_loop(self, monkeypatch):
        import disq_tpu.cram.codec as codec_mod

        decode_slice, hdr, comp, blocks, flags, rl = self._shared_slice()
        outcome = {}
        real = codec_mod._bulk_fixed_series

        def spy(*a, **k):
            r = real(*a, **k)
            outcome["bulk"] = r is not None
            return r

        monkeypatch.setattr(codec_mod, "_bulk_fixed_series", spy)
        batch = decode_slice(hdr, comp, blocks, b"", None)
        assert outcome == {"bulk": False}  # shared cid -> declined
        assert batch.count == 3
        np.testing.assert_array_equal(batch.flag, flags)
        np.testing.assert_array_equal(np.diff(batch.seq_offsets), rl)
        np.testing.assert_array_equal(batch.pos, [10, 20, -1])
        # QS bytes arrive intact through the per-record path
        np.testing.assert_array_equal(
            batch.quals, np.arange(sum(rl), dtype=np.uint8))

    def test_eligibility_gates_directly(self):
        from disq_tpu.cram.codec import (
            CID,
            E_BYTE_ARRAY_STOP,
            E_EXTERNAL,
            Encoding,
            _bulk_split_names,
            _external_cids_excluding,
        )

        ext = lambda cid: Encoding(E_EXTERNAL, cid)  # noqa: E731

        class _Comp:
            tag_enc = {0x4E4D43: ext(77)}

        enc = {"RN": Encoding(E_BYTE_ARRAY_STOP, (0, 77)), "BF": ext(1)}
        used = _external_cids_excluding(_Comp, enc, ("RN",))
        assert 77 in used and 1 in used  # tag shares RN's block

        class _Rd:
            cur = {}

        class _Comp2:
            tag_enc = {}
            rn_preserved = True

        assert _bulk_split_names(_Rd, _Comp2, enc, 5) is None
