// disq_tpu native host runtime.
//
// The hot host-side loops behind the JAX/device pipeline:
//   - BAM record-offset scan (the block_size chain walk — sequential by
//     nature, so it belongs in C, not Python)
//   - batched BGZF block inflate (one raw-DEFLATE stream per block,
//     embarrassingly parallel across blocks -> thread pool)
//   - batched canonical BGZF deflate for the write path (zlib level 6,
//     memLevel 8 — must stay byte-identical to the Python codec's pin in
//     disq_tpu/bgzf/codec.py)
//
// Replaces the role htsjdk's BlockCompressedInputStream/OutputStream +
// BAMRecordCodec inner loops play for the reference (SURVEY.md §2.8).
//
// Build: g++ -O3 -shared -fPIC -std=c++17 disq_host.cpp -o libdisq_host.so -lz -pthread

#include <algorithm>
#include <atomic>
#include <cstdint>
#include <cstring>
#include <thread>
#include <vector>

#include <zlib.h>

// Inflate/CRC fast path. libdeflate is ~2-3x faster than zlib at raw
// DEFLATE decode and is a pure read-side accelerator: the payload bytes
// produced are identical, so byte-identity pins are unaffected. The
// write path stays on zlib (level 6, memLevel 8) unconditionally — its
// output bytes ARE the canonical pin. The Python builder first compiles
// with -DDISQ_HAVE_LIBDEFLATE -ldeflate and retries without on failure.
#ifdef DISQ_HAVE_LIBDEFLATE
#include <libdeflate.h>
#define DISQ_VARIANT "libdeflate"
#else
#define DISQ_VARIANT "zlib"
#endif

// The Python builder passes the SHA-256 of this file; the loader finds
// this string in the library's bytes and compares it with the source on
// disk, so a library that was copied (mtimes lost) or built from an
// older source is rebuilt, never reused.
#ifndef DISQ_SRC_HASH
#define DISQ_SRC_HASH "unknown"
#endif

extern "C" {

// "disq-build:<source sha256>:<inflate variant>:" (exported, so the
// linker keeps it).
extern const char disq_build_info[] =
    "disq-build:" DISQ_SRC_HASH ":" DISQ_VARIANT ":";

// Walk the BAM record chain: buf holds concatenated records; writes up to
// max_out offsets (of each record start) into out_offsets and finally the
// end offset. Returns the number of records, or -1-errpos on corruption.
int64_t disq_scan_bam_offsets(const uint8_t* buf, int64_t len,
                              int64_t* out_offsets, int64_t max_out) {
  int64_t pos = 0;
  int64_t n = 0;
  while (pos + 4 <= len) {
    int32_t block_size;
    std::memcpy(&block_size, buf + pos, 4);
    int64_t nxt = pos + 4 + (int64_t)block_size;
    if (block_size < 32 || nxt > len) return -1 - pos;
    if (n >= max_out) return -1 - pos;
    out_offsets[n++] = pos;
    pos = nxt;
  }
  if (pos != len) return -1 - pos;
  out_offsets[n] = len;  // caller allocates max_out+1
  return n;
}

// Walk BGZF block headers in a staged buffer that begins at a block
// start. Records every block whose header starts before `stop` and whose
// complete bytes (through the 8-byte footer) lie within the buffer:
// rel_pos[i] (offset of block i's gzip header within buf), csize[i]
// (total block length), usize[i] (ISIZE from the footer). Stops cleanly
// at the first block that straddles the buffer end (the caller re-reads
// from there). Returns the block count, or -1-pos on a malformed header.
int64_t disq_bgzf_walk(const uint8_t* buf, int64_t len, int64_t stop,
                       int64_t* rel_pos, int32_t* csize, int32_t* usize,
                       int64_t max_out) {
  int64_t p = 0, n = 0;
  while (p < stop && n < max_out) {
    if (p + 18 > len) break;  // not even a fixed header + BC subfield
    if (buf[p] != 0x1f || buf[p + 1] != 0x8b || buf[p + 2] != 0x08 ||
        (buf[p + 3] & 0x04) == 0)
      return -1 - p;
    uint16_t xlen;
    std::memcpy(&xlen, buf + p + 10, 2);
    if (p + 12 + xlen > len) break;
    int32_t bsize = -1;
    int64_t q = p + 12, qend = p + 12 + xlen;
    while (q + 4 <= qend) {
      uint16_t slen;
      std::memcpy(&slen, buf + q + 2, 2);
      if (buf[q] == 0x42 && buf[q + 1] == 0x43 && slen == 2) {
        if (q + 6 > qend) return -1 - p;  // BC payload truncated
        uint16_t bs;
        std::memcpy(&bs, buf + q + 4, 2);
        bsize = (int32_t)bs + 1;
      }
      q += 4 + slen;
    }
    if (bsize < 12 + xlen + 8) return -1 - p;
    if (p + bsize > len) break;  // block straddles the buffer end
    rel_pos[n] = p;
    csize[n] = bsize;
    std::memcpy(&usize[n], buf + p + bsize - 4, 4);
    n++;
    p += bsize;
  }
  return n;
}

// Count records without storing offsets (for sizing).
int64_t disq_count_bam_records(const uint8_t* buf, int64_t len) {
  int64_t pos = 0, n = 0;
  while (pos + 4 <= len) {
    int32_t block_size;
    std::memcpy(&block_size, buf + pos, 4);
    int64_t nxt = pos + 4 + (int64_t)block_size;
    if (block_size < 32 || nxt > len) return -1 - pos;
    n++;
    pos = nxt;
  }
  if (pos != len) return -1 - pos;
  return n;
}

#ifndef DISQ_HAVE_LIBDEFLATE
static int inflate_one(const uint8_t* src, uint32_t csize, uint8_t* dst,
                       uint32_t usize) {
  z_stream zs;
  std::memset(&zs, 0, sizeof(zs));
  if (inflateInit2(&zs, -15) != Z_OK) return 1;
  zs.next_in = const_cast<uint8_t*>(src);
  zs.avail_in = csize;
  zs.next_out = dst;
  zs.avail_out = usize;
  int ret = inflate(&zs, Z_FINISH);
  uint32_t got = usize - zs.avail_out;
  inflateEnd(&zs);
  if (ret != Z_STREAM_END || got != usize) return 2;
  return 0;
}
#endif

// CRC32 of decoded blocks where they lie: block idx[k] is
// blob[offsets[idx[k]], offsets[idx[k] + 1]) and has to have the CRC32
// expect[idx[k]] (its BGZF footer's). Returns the position k of the
// first block that differs, or -1. One call a batch: the caller (the
// decode service's per-launch check) holds no interpreter lock inside
// it, where a zlib.crc32 a block takes and drops the lock a block.
int64_t disq_crc32_check(const uint8_t* blob, const int64_t* offsets,
                         const int64_t* idx, int64_t n,
                         const uint32_t* expect) {
  for (int64_t k = 0; k < n; ++k) {
    const int64_t i = idx[k];
    const uint8_t* p = blob + offsets[i];
    const size_t len = (size_t)(offsets[i + 1] - offsets[i]);
#ifdef DISQ_HAVE_LIBDEFLATE
    const uint32_t got = libdeflate_crc32(0, p, len);
#else
    const uint32_t got = (uint32_t)crc32(0L, p, (uInt)len);
#endif
    if (got != expect[i]) return k;
  }
  return -1;
}

// Batched BGZF inflate. data: staged compressed bytes; block_off[i] is the
// offset of block i's *gzip header* within data; hdr_len[i] the header
// length (12+XLEN); csize[i] the total block size; usize[i] the payload's
// uncompressed size. Output written at out + out_off[i]. check_crc != 0
// verifies each block's CRC32. Returns 0 or the 1-based index of the
// first failing block (negated for CRC failures).
int64_t disq_bgzf_inflate_many(const uint8_t* data, const int64_t* block_off,
                               const int32_t* hdr_len, const int32_t* csize,
                               const int32_t* usize, int64_t nblocks,
                               uint8_t* out, const int64_t* out_off,
                               int32_t check_crc, int32_t nthreads) {
  std::atomic<int64_t> next(0);
  std::atomic<int64_t> fail(0);
  // First error wins; later workers must not overwrite it (the alloc
  // sentinel nblocks+1 and a real block error are different classes).
  auto set_fail = [&](int64_t code) {
    int64_t expected = 0;
    fail.compare_exchange_strong(expected, code);
  };
  auto worker = [&]() {
#ifdef DISQ_HAVE_LIBDEFLATE
    struct libdeflate_decompressor* dec = libdeflate_alloc_decompressor();
    if (dec == nullptr) {
      set_fail(nblocks + 1);  // alloc-failure sentinel, see Python binding
      return;
    }
#endif
    for (;;) {
      int64_t i = next.fetch_add(1);
      if (i >= nblocks || fail.load() != 0) break;
      const uint8_t* src = data + block_off[i] + hdr_len[i];
      uint32_t comp_len = (uint32_t)csize[i] - (uint32_t)hdr_len[i] - 8;
      uint8_t* dst = out + out_off[i];
#ifdef DISQ_HAVE_LIBDEFLATE
      size_t got_sz = 0;
      if (libdeflate_deflate_decompress(dec, src, comp_len, dst,
                                        (size_t)usize[i],
                                        &got_sz) != LIBDEFLATE_SUCCESS ||
          got_sz != (size_t)usize[i]) {
        set_fail(i + 1);
        break;
      }
#else
      if (inflate_one(src, comp_len, dst, (uint32_t)usize[i]) != 0) {
        set_fail(i + 1);
        break;
      }
#endif
      if (check_crc) {
        uint32_t want;
        std::memcpy(&want, data + block_off[i] + csize[i] - 8, 4);
#ifdef DISQ_HAVE_LIBDEFLATE
        uint32_t got = libdeflate_crc32(0, dst, (size_t)usize[i]);
#else
        uint32_t got = crc32(0L, dst, (uint32_t)usize[i]);
#endif
        if (got != want) {
          set_fail(-(i + 1));
          break;
        }
      }
    }
#ifdef DISQ_HAVE_LIBDEFLATE
    libdeflate_free_decompressor(dec);
#endif
  };
  int nt = nthreads > 0 ? nthreads : 1;
  if (nt == 1 || nblocks < 4) {
    worker();
  } else {
    std::vector<std::thread> threads;
    for (int t = 0; t < nt; t++) threads.emplace_back(worker);
    for (auto& th : threads) th.join();
  }
  return fail.load();
}

// Batched canonical BGZF deflate. payload split into blocks by pay_off
// (nblocks+1 entries); block i's complete BGZF bytes (18-byte header +
// deflate stream + 8-byte footer) are written at out + i*out_stride, its
// total size into out_sizes[i]. Uses zlib level `level`, memLevel 8 —
// byte-identical to the Python pin. Falls back to stored (level 0) when
// the compressed block would exceed 64 KiB. Returns 0 or 1-based failing
// block index.
int64_t disq_bgzf_deflate_many(const uint8_t* payload, const int64_t* pay_off,
                               int64_t nblocks, uint8_t* out,
                               int64_t out_stride, int32_t* out_sizes,
                               int32_t level, int32_t nthreads) {
  static const uint8_t HDR[16] = {0x1f, 0x8b, 0x08, 0x04, 0, 0, 0, 0,
                                  0,    0xff, 0x06, 0x00, 0x42, 0x43, 0x02, 0x00};
  std::atomic<int64_t> next(0);
  std::atomic<int64_t> fail(0);
  auto worker = [&]() {
    for (;;) {
      int64_t i = next.fetch_add(1);
      if (i >= nblocks || fail.load() != 0) return;
      const uint8_t* src = payload + pay_off[i];
      uint32_t plen = (uint32_t)(pay_off[i + 1] - pay_off[i]);
      uint8_t* blk = out + i * out_stride;
      for (int attempt = 0; attempt < 2; attempt++) {
        int lvl = attempt == 0 ? level : 0;
        z_stream zs;
        std::memset(&zs, 0, sizeof(zs));
        if (deflateInit2(&zs, lvl, Z_DEFLATED, -15, 8, Z_DEFAULT_STRATEGY) !=
            Z_OK) {
          fail.store(i + 1);
          return;
        }
        zs.next_in = const_cast<uint8_t*>(src);
        zs.avail_in = plen;
        zs.next_out = blk + 18;
        zs.avail_out = (uint32_t)(out_stride - 26);
        int ret = deflate(&zs, Z_FINISH);
        uint32_t clen = (uint32_t)(out_stride - 26 - zs.avail_out);
        deflateEnd(&zs);
        if (ret != Z_STREAM_END) {
          if (attempt == 0) continue;  // retry stored
          fail.store(i + 1);
          return;
        }
        uint32_t total = 18 + clen + 8;
        if (total > 0x10000) {
          if (attempt == 0) continue;  // retry stored
          fail.store(i + 1);
          return;
        }
        std::memcpy(blk, HDR, 16);
        uint16_t bsize = (uint16_t)(total - 1);
        std::memcpy(blk + 16, &bsize, 2);
        uint32_t crc = crc32(0L, src, plen);
        std::memcpy(blk + 18 + clen, &crc, 4);
        std::memcpy(blk + 18 + clen + 4, &plen, 4);
        out_sizes[i] = (int32_t)total;
        break;
      }
    }
  };
  int nt = nthreads > 0 ? nthreads : 1;
  if (nt == 1 || nblocks < 4) {
    worker();
  } else {
    std::vector<std::thread> threads;
    for (int t = 0; t < nt; t++) threads.emplace_back(worker);
    for (auto& th : threads) th.join();
  }
  return fail.load();
}

// -- columnar record codec ---------------------------------------------------
// Pass 2 of the BAM decode (disq_tpu/bam/codec.py): one sequential,
// cache-friendly pass over the record blob replacing numpy's per-column
// index-array gathers. Layout per record after the 4-byte block_size:
// refID i32 · pos i32 · l_read_name u8 · mapq u8 · bin u16 · n_cigar u16 ·
// flag u16 · l_seq i32 · next_refID i32 · next_pos i32 · tlen i32 ·
// name · cigar · packed seq · qual · tags.

// Phase A: extract fixed columns + section lengths (for offset cumsums).
int64_t disq_bam_fixed_columns(const uint8_t* buf, int64_t buf_len,
                               const int64_t* offsets,
                               int64_t n, int32_t* refid, int32_t* pos,
                               uint8_t* mapq, uint16_t* bin, uint16_t* flag,
                               int32_t* next_refid, int32_t* next_pos,
                               int32_t* tlen, int64_t* name_len,
                               int64_t* n_cigar, int64_t* l_seq,
                               int64_t* tag_len) {
  for (int64_t i = 0; i < n; i++) {
    // Bounds before any read: caller-supplied offsets are untrusted.
    if (offsets[i] < 0 || offsets[i + 1] < offsets[i] + 36 ||
        offsets[i + 1] > buf_len)
      return -1 - i;
    const uint8_t* r = buf + offsets[i];
    int32_t v32;
    uint16_t v16;
    std::memcpy(&v32, r + 4, 4); refid[i] = v32;
    std::memcpy(&v32, r + 8, 4); pos[i] = v32;
    uint8_t lrn = r[12];
    mapq[i] = r[13];
    std::memcpy(&v16, r + 14, 2); bin[i] = v16;
    uint16_t nc;
    std::memcpy(&nc, r + 16, 2);
    std::memcpy(&v16, r + 18, 2); flag[i] = v16;
    int32_t ls;
    std::memcpy(&ls, r + 20, 4);
    std::memcpy(&v32, r + 24, 4); next_refid[i] = v32;
    std::memcpy(&v32, r + 28, 4); next_pos[i] = v32;
    std::memcpy(&v32, r + 32, 4); tlen[i] = v32;
    if (lrn < 1 || ls < 0) return -1 - i;
    name_len[i] = lrn - 1;
    n_cigar[i] = nc;
    l_seq[i] = ls;
    int64_t sections = 32 + lrn + 4LL * nc + (ls + 1) / 2 + ls;
    int64_t rec_len = offsets[i + 1] - offsets[i] - 4;
    if (sections > rec_len) return -1 - i;
    tag_len[i] = rec_len - sections;
  }
  return 0;
}

// Reference bases the ``nc`` CIGAR op words at ``c`` consume.
static inline int64_t cigar_reference_length(const uint8_t* c, uint16_t nc) {
  int64_t len = 0;
  for (uint16_t k = 0; k < nc; k++, c += 4) {
    uint32_t w;
    std::memcpy(&w, c, 4);
    // M=0 D=2 N=3 '='=7 X=8
    if ((0x18Du >> (w & 0xF)) & 1) len += w >> 4;
  }
  return len;
}

// CIGAR-only pass: each record's pos and the reference length its
// CIGAR consumes (ops M, D, N, =, X), reading 36 fixed bytes and the
// op words of a record and nothing else of it.  ``offsets`` index a
// larger blob of which ``buf`` is the part that starts at ``base``;
// ``ops`` takes the op words walked.
int64_t disq_bam_reference_lengths(const uint8_t* buf, int64_t buf_len,
                                   const int64_t* offsets, int64_t base,
                                   int64_t n, int32_t* pos,
                                   int64_t* reflen, int64_t* ops) {
  *ops = 0;
  for (int64_t i = 0; i < n; i++) {
    int64_t at = offsets[i] - base, end = offsets[i + 1] - base;
    if (at < 0 || end < at + 36 || end > buf_len) return -1 - i;
    const uint8_t* r = buf + at;
    uint16_t nc;
    std::memcpy(&nc, r + 16, 2);
    if (36 + (int64_t)r[12] + 4LL * nc > end - at) return -1 - i;
    std::memcpy(pos + i, r + 8, 4);
    reflen[i] = cigar_reference_length(r + 36 + r[12], nc);
    *ops += nc;
  }
  return 0;
}

// Duplicate-marking keys in one sweep: each record's pos, the reference
// length its CIGAR consumes, the clipped bases (S=4 / H=5) at either end
// and its score, the sum of its base qualities >= 15 (0xFF, "no
// qualities", scores 0).  Memory is the five outputs: nothing here is
// records x read length.  A clip counts at an end only as that end's
// outermost op, or as the next one in when the outermost was a clip too
// (H then S), as ``ops/markdup.clip_and_span`` has it.
int64_t disq_bam_markdup_keys(const uint8_t* buf, int64_t buf_len,
                              const int64_t* offsets, int64_t n,
                              int32_t* pos, int64_t* reflen, int64_t* lead,
                              int64_t* trail, int64_t* score) {
  auto clip = [](uint32_t w) { return (w & 0xF) == 4 || (w & 0xF) == 5; };
  for (int64_t i = 0; i < n; i++) {
    int64_t at = offsets[i], end = offsets[i + 1];
    if (at < 0 || end < at + 36 || end > buf_len) return -1 - i;
    const uint8_t* r = buf + at;
    uint16_t nc;
    int32_t ls;
    std::memcpy(&nc, r + 16, 2);
    std::memcpy(&ls, r + 20, 4);
    if (ls < 0) return -1 - i;
    int64_t cig = 36 + (int64_t)r[12], qual = cig + 4LL * nc + (ls + 1) / 2;
    if (qual + ls > end - at) return -1 - i;
    std::memcpy(pos + i, r + 8, 4);
    const uint8_t* c = r + cig;
    reflen[i] = cigar_reference_length(c, nc);
    int64_t lc = 0, tc = 0;
    uint32_t w0, w1;
    if (nc > 0) {
      std::memcpy(&w0, c, 4);
      if (clip(w0)) {
        lc = w0 >> 4;
        if (nc > 1) {
          std::memcpy(&w1, c + 4, 4);
          if (clip(w1)) lc += w1 >> 4;
        }
      }
      std::memcpy(&w0, c + 4 * (nc - 1), 4);
      if (clip(w0)) {
        tc = w0 >> 4;
        if (nc > 1) {
          std::memcpy(&w1, c + 4 * (nc - 2), 4);
          if (clip(w1)) tc += w1 >> 4;
        }
      }
    }
    lead[i] = lc;
    trail[i] = tc;
    const uint8_t* q = r + qual;
    int64_t sum = 0;
    for (int32_t k = 0; k < ls; k++)
      if (q[k] >= 15 && q[k] != 0xFF) sum += q[k];
    score[i] = sum;
  }
  return 0;
}

// Phase B: fill ragged columns (seq unpacked to one nibble code per byte).
int64_t disq_bam_fill_ragged(const uint8_t* buf, const int64_t* offsets,
                             int64_t n, const int64_t* name_off,
                             uint8_t* names, const int64_t* cigar_off,
                             uint32_t* cigars, const int64_t* seq_off,
                             uint8_t* seqs, uint8_t* quals,
                             const int64_t* tag_off, uint8_t* tags) {
  for (int64_t i = 0; i < n; i++) {
    const uint8_t* r = buf + offsets[i];
    uint8_t lrn = r[12];
    int64_t nc = cigar_off[i + 1] - cigar_off[i];
    int64_t ls = seq_off[i + 1] - seq_off[i];
    const uint8_t* p = r + 36;
    std::memcpy(names + name_off[i], p, lrn - 1);
    p += lrn;
    std::memcpy(cigars + cigar_off[i], p, 4 * nc);
    p += 4 * nc;
    uint8_t* sq = seqs + seq_off[i];
    for (int64_t k = 0; k + 1 < ls; k += 2) {
      uint8_t b = p[k >> 1];
      sq[k] = b >> 4;
      sq[k + 1] = b & 0xF;
    }
    if (ls & 1) sq[ls - 1] = p[(ls - 1) >> 1] >> 4;
    p += (ls + 1) / 2;
    std::memcpy(quals + seq_off[i], p, ls);
    p += ls;
    std::memcpy(tags + tag_off[i], p, tag_off[i + 1] - tag_off[i]);
  }
  return 0;
}

// Encode: columns -> record bytes, one pass (inverse of the above).
// rec_off[i] gives each record's output start (precomputed cumsum).
int64_t disq_bam_encode(uint8_t* out, const int64_t* rec_off, int64_t n,
                        const int32_t* refid, const int32_t* pos,
                        const uint8_t* mapq, const uint16_t* bin,
                        const uint16_t* flag, const int32_t* next_refid,
                        const int32_t* next_pos, const int32_t* tlen,
                        const int64_t* name_off, const uint8_t* names,
                        const int64_t* cigar_off, const uint32_t* cigars,
                        const int64_t* seq_off, const uint8_t* seqs,
                        const uint8_t* quals, const int64_t* tag_off,
                        const uint8_t* tags) {
  for (int64_t i = 0; i < n; i++) {
    uint8_t* r = out + rec_off[i];
    int64_t nl = name_off[i + 1] - name_off[i];
    int64_t nc = cigar_off[i + 1] - cigar_off[i];
    int64_t ls = seq_off[i + 1] - seq_off[i];
    int64_t tl = tag_off[i + 1] - tag_off[i];
    if (nl > 254 || nc > 0xFFFF) return -1 - i;
    int32_t block_size =
        (int32_t)(32 + (nl + 1) + 4 * nc + (ls + 1) / 2 + ls + tl);
    std::memcpy(r, &block_size, 4);
    std::memcpy(r + 4, refid + i, 4);
    std::memcpy(r + 8, pos + i, 4);
    r[12] = (uint8_t)(nl + 1);
    r[13] = mapq[i];
    std::memcpy(r + 14, bin + i, 2);
    uint16_t nc16 = (uint16_t)nc;
    std::memcpy(r + 16, &nc16, 2);
    std::memcpy(r + 18, flag + i, 2);
    int32_t ls32 = (int32_t)ls;
    std::memcpy(r + 20, &ls32, 4);
    std::memcpy(r + 24, next_refid + i, 4);
    std::memcpy(r + 28, next_pos + i, 4);
    std::memcpy(r + 32, tlen + i, 4);
    uint8_t* p = r + 36;
    std::memcpy(p, names + name_off[i], nl);
    p[nl] = 0;
    p += nl + 1;
    std::memcpy(p, cigars + cigar_off[i], 4 * nc);
    p += 4 * nc;
    const uint8_t* sq = seqs + seq_off[i];
    for (int64_t k = 0; k + 1 < ls; k += 2)
      p[k >> 1] = (uint8_t)((sq[k] << 4) | (sq[k + 1] & 0xF));
    if (ls & 1) p[(ls - 1) >> 1] = (uint8_t)(sq[ls - 1] << 4);
    p += (ls + 1) / 2;
    std::memcpy(p, quals + seq_off[i], ls);
    p += ls;
    std::memcpy(p, tags + tag_off[i], tl);
  }
  return 0;
}

}  // extern "C"

// ---------------------------------------------------------------------------
// rANS 4x8 (CRAM 3.0 §13) — native port of disq_tpu/cram/rans.py.
// Order-0 encode/decode + order-1 decode; stream layout matches
// htslib's rANS_static (order u8, comp_size u32, raw_size u32, freq
// table, 4 interleaved u32 states, renorm bytes).

static const int kTfShift = 12;
static const int kTotFreq = 1 << kTfShift;  // 4096
static const uint32_t kRansLow = 1u << 23;

// Mirror of _normalize_freqs: floor-scale, clamp present symbols to >=1,
// then fix the total by walking symbols in stable descending-frequency
// order (ties by symbol index) — byte-identical tables to the Python pin.
static void rans_normalize(const int64_t* counts, int64_t* out) {
  int64_t n = 0;
  for (int s = 0; s < 256; s++) n += counts[s];
  if (n == 0) {
    for (int s = 0; s < 256; s++) out[s] = 0;
    return;
  }
  int64_t sum = 0;
  for (int s = 0; s < 256; s++) {
    double f = (double)counts[s] * kTotFreq / (double)n;
    out[s] = (int64_t)f;  // floor for non-negative
    if (counts[s] > 0 && out[s] == 0) out[s] = 1;
    sum += out[s];
  }
  int idx[256];
  for (int s = 0; s < 256; s++) idx[s] = s;
  std::stable_sort(idx, idx + 256,
                   [&](int a, int b) { return out[a] > out[b]; });
  int64_t diff = kTotFreq - sum;
  int64_t i = 0;
  while (diff != 0) {
    int s = idx[i % 256];
    if (out[s] > 0 || diff > 0) {
      int64_t step = diff > 0 ? 1 : -1;
      if (out[s] + step >= 1 || counts[s] == 0) {
        out[s] += step;
        diff -= step;
      }
    }
    i++;
  }
}

static int64_t rans_write_table0(const int64_t* freqs, uint8_t* out) {
  int syms[256];
  int ns = 0;
  for (int s = 0; s < 256; s++)
    if (freqs[s]) syms[ns++] = s;
  int64_t p = 0;
  int rle = 0;
  for (int k = 0; k < ns; k++) {
    int s = syms[k];
    if (rle > 0) {
      rle--;
    } else {
      out[p++] = (uint8_t)s;
      if (k > 0 && s == syms[k - 1] + 1) {
        int run = 0;
        while (k + run + 1 < ns && syms[k + run + 1] == s + run + 1) run++;
        out[p++] = (uint8_t)run;
        rle = run;
      }
    }
    int64_t f = freqs[s];
    if (f < 128) {
      out[p++] = (uint8_t)f;
    } else {
      out[p++] = (uint8_t)(0x80 | (f >> 8));
      out[p++] = (uint8_t)(f & 0xFF);
    }
  }
  out[p++] = 0;
  return p;
}

static int64_t rans_read_table0(const uint8_t* d, int64_t len, int64_t off,
                                int64_t* freqs) {
  for (int s = 0; s < 256; s++) freqs[s] = 0;
  if (off >= len) return -1;
  int rle = 0;
  int sym = d[off++];
  int last;
  for (;;) {
    if (off >= len) return -1;
    int64_t f = d[off++];
    if (f >= 128) {
      if (off >= len) return -1;
      f = ((f & 0x7F) << 8) | d[off++];
    }
    if (sym > 255) return -1;
    freqs[sym] = f;
    if (rle > 0) {
      rle--;
      last = sym;
      sym = sym + 1;
      (void)last;
      continue;
    }
    last = sym;
    if (off >= len) return -1;
    int nxt = d[off++];
    if (nxt == 0) break;
    if (nxt == last + 1) {
      if (off >= len) return -1;
      rle = d[off++];
    }
    sym = nxt;
  }
  return off;
}

extern "C" {

// Order-0 encode. Returns total stream length (9-byte header + body),
// or -1 when out_cap is too small. raw may be empty.
int64_t disq_rans_encode0(const uint8_t* raw, int64_t n, uint8_t* out,
                          int64_t out_cap) {
  if (n == 0) {
    if (out_cap < 9) return -1;
    out[0] = 0;
    std::memset(out + 1, 0, 8);
    return 9;
  }
  int64_t counts[256] = {0};
  for (int64_t i = 0; i < n; i++) counts[raw[i]]++;
  int64_t freqs[256];
  rans_normalize(counts, freqs);
  int64_t cum[257];
  cum[0] = 0;
  for (int s = 0; s < 256; s++) cum[s + 1] = cum[s] + freqs[s];
  if (out_cap < 9 + 771 + 16 + (n * 3) / 2 + 64) return -1;
  uint8_t* body = out + 9;
  int64_t p = rans_write_table0(freqs, body);
  // Encode in reverse; renorm bytes are emitted reversed then flipped.
  std::vector<uint8_t> rev;
  rev.reserve((size_t)n / 2);
  uint32_t states[4] = {kRansLow, kRansLow, kRansLow, kRansLow};
  for (int64_t i = n - 1; i >= 0; i--) {
    int s = raw[i];
    int j = (int)(i & 3);
    uint32_t x = states[j];
    uint32_t f = (uint32_t)freqs[s];
    uint32_t x_max = ((kRansLow >> kTfShift) << 8) * f;
    while (x >= x_max) {
      rev.push_back((uint8_t)(x & 0xFF));
      x >>= 8;
    }
    states[j] = ((x / f) << kTfShift) + (x % f) + (uint32_t)cum[s];
  }
  for (int j = 0; j < 4; j++) {
    std::memcpy(body + p, &states[j], 4);
    p += 4;
  }
  for (int64_t k = (int64_t)rev.size() - 1; k >= 0; k--) body[p++] = rev[k];
  out[0] = 0;
  uint32_t comp = (uint32_t)p, rs = (uint32_t)n;
  std::memcpy(out + 1, &comp, 4);
  std::memcpy(out + 5, &rs, 4);
  return 9 + p;
}

// Order-1 encode: 4 interleaved states over contiguous quarters,
// context = previous byte (0 at each quarter start); context tables
// serialized with RLE-over-contexts. Byte-identical to
// disq_tpu/cram/rans.py rans_encode_order1 (the htslib wire format the
// decoder below already reads).
int64_t disq_rans_encode1(const uint8_t* raw, int64_t n, uint8_t* out,
                          int64_t out_cap) {
  if (n == 0) {
    if (out_cap < 9) return -1;
    out[0] = 1;
    std::memset(out + 1, 0, 8);
    return 9;
  }
  int64_t q = n / 4;
  int64_t starts[4] = {0, q, 2 * q, 3 * q};
  int64_t ends[4] = {q, 2 * q, 3 * q, n};
  std::vector<int64_t> counts((size_t)256 * 256, 0);
  for (int j = 0; j < 4; j++) {
    uint8_t prev = 0;
    for (int64_t p2 = starts[j]; p2 < ends[j]; p2++) {
      counts[(size_t)prev * 256 + raw[p2]]++;
      prev = raw[p2];
    }
  }
  std::vector<int64_t> freqs((size_t)256 * 256, 0);
  std::vector<int64_t> cum((size_t)256 * 257, 0);
  bool present[256] = {false};
  for (int c = 0; c < 256; c++) {
    int64_t tot = 0;
    for (int s = 0; s < 256; s++) tot += counts[(size_t)c * 256 + s];
    if (!tot) continue;
    present[c] = true;
    rans_normalize(&counts[(size_t)c * 256], &freqs[(size_t)c * 256]);
    for (int s = 0; s < 256; s++)
      cum[(size_t)c * 257 + s + 1] =
          cum[(size_t)c * 257 + s] + freqs[(size_t)c * 256 + s];
  }
  // worst-case table area: 256 contexts x (ids + 771-byte table)
  if (out_cap < 9 + 256 * 775 + 16 + (n * 3) / 2 + 64) return -1;
  uint8_t* body = out + 9;
  int64_t p = 0;
  int plist[256];
  int np_ = 0;
  for (int c = 0; c < 256; c++)
    if (present[c]) plist[np_++] = c;
  int i = 0;
  while (i < np_) {
    int run = 1;
    while (i + run < np_ && plist[i + run] == plist[i] + run) run++;
    body[p++] = (uint8_t)plist[i];
    p += rans_write_table0(&freqs[(size_t)plist[i] * 256], body + p);
    if (run > 1) {
      // parser: nxt == last+1 -> read an rle count, then auto-advance
      body[p++] = (uint8_t)(plist[i] + 1);
      body[p++] = (uint8_t)(run - 2);
      for (int k = 1; k < run; k++)
        p += rans_write_table0(&freqs[(size_t)(plist[i] + k) * 256],
                               body + p);
    }
    i += run;
  }
  body[p++] = 0;
  // encode: exact reverse of the decoder's round-robin pop schedule
  int64_t lens[4];
  for (int j = 0; j < 4; j++) lens[j] = ends[j] - starts[j];
  int64_t kmax = 0;
  for (int j = 0; j < 4; j++)
    if (lens[j] > kmax) kmax = lens[j];
  std::vector<uint8_t> rev;
  rev.reserve((size_t)n / 2);
  uint32_t states[4] = {kRansLow, kRansLow, kRansLow, kRansLow};
  for (int64_t k = kmax - 1; k >= 0; k--) {
    for (int j = 3; j >= 0; j--) {
      if (k >= lens[j]) continue;
      int64_t pos = starts[j] + k;
      int s = raw[pos];
      int c = (k == 0) ? 0 : raw[pos - 1];
      uint32_t x = states[j];
      uint32_t f = (uint32_t)freqs[(size_t)c * 256 + s];
      uint32_t x_max = ((kRansLow >> kTfShift) << 8) * f;
      while (x >= x_max) {
        rev.push_back((uint8_t)(x & 0xFF));
        x >>= 8;
      }
      states[j] =
          ((x / f) << kTfShift) + (x % f) + (uint32_t)cum[(size_t)c * 257 + s];
    }
  }
  for (int j = 0; j < 4; j++) {
    std::memcpy(body + p, &states[j], 4);
    p += 4;
  }
  for (int64_t k = (int64_t)rev.size() - 1; k >= 0; k--) body[p++] = rev[k];
  out[0] = 1;
  uint32_t comp = (uint32_t)p, rs = (uint32_t)n;
  std::memcpy(out + 1, &comp, 4);
  std::memcpy(out + 5, &rs, 4);
  return 9 + p;
}

// Decode (order 0 or 1). data = full stream incl. 9-byte header; out
// must hold raw_size bytes (as announced in the header — the caller
// reads it first). Returns 0, or a negative error code.
int64_t disq_rans_decode(const uint8_t* data, int64_t len, uint8_t* out,
                         int64_t out_len) {
  if (len < 9) return -2;
  int order = data[0];
  uint32_t comp_size, raw_size;
  std::memcpy(&comp_size, data + 1, 4);
  std::memcpy(&raw_size, data + 5, 4);
  if (raw_size == 0) return 0;
  if ((int64_t)raw_size != out_len) return -3;
  const uint8_t* body = data + 9;
  int64_t blen = comp_size;
  if (9 + blen > len) return -4;

  if (order == 0) {
    int64_t freqs[256];
    int64_t off = rans_read_table0(body, blen, 0, freqs);
    if (off < 0) return -5;
    int64_t cum[257];
    cum[0] = 0;
    for (int s = 0; s < 256; s++) cum[s + 1] = cum[s] + freqs[s];
    if (cum[256] != kTotFreq) return -6;
    uint8_t lookup[kTotFreq];
    for (int s = 0; s < 256; s++)
      for (int64_t k = cum[s]; k < cum[s + 1]; k++) lookup[k] = (uint8_t)s;
    if (off + 16 > blen) return -4;
    uint32_t states[4];
    for (int j = 0; j < 4; j++) {
      std::memcpy(&states[j], body + off, 4);
      off += 4;
    }
    for (int64_t i = 0; i < (int64_t)raw_size; i++) {
      int j = (int)(i & 3);
      uint32_t x = states[j];
      uint32_t m = x & (kTotFreq - 1);
      int s = lookup[m];
      out[i] = (uint8_t)s;
      x = (uint32_t)freqs[s] * (x >> kTfShift) + m - (uint32_t)cum[s];
      // A valid stream always has the renorm byte it needs (final states
      // land exactly at kRansLow); a deficit means the body is truncated.
      while (x < kRansLow) {
        if (off >= blen) return -8;
        x = (x << 8) | body[off++];
      }
      states[j] = x;
    }
    return 0;
  }

  if (order == 1) {
    // Context tables, RLE over contexts like the symbol list.
    static_assert(sizeof(int64_t) == 8, "");
    std::vector<int64_t> freqs(256 * 256, 0);
    std::vector<int64_t> cum(256 * 257, 0);
    std::vector<uint8_t> lookups(256 * kTotFreq);
    std::vector<bool> built(256, false);
    int64_t off = 0;
    int rle_i = 0;
    if (blen < 1) return -4;
    int i = body[off++];
    int last_i;
    for (;;) {
      off = rans_read_table0(body, blen, off, &freqs[(int64_t)i * 256]);
      if (off < 0) return -5;
      if (rle_i > 0) {
        rle_i--;
        last_i = i;
        i++;
        if (i > 255) return -5;
        continue;
      }
      last_i = i;
      if (off >= blen) return -4;
      int nxt = body[off++];
      if (nxt == 0) break;
      if (nxt == last_i + 1) {
        if (off >= blen) return -4;
        rle_i = body[off++];
      }
      i = nxt;
    }
    for (int c = 0; c < 256; c++) {
      int64_t* cm = &cum[(int64_t)c * 257];
      const int64_t* fr = &freqs[(int64_t)c * 256];
      cm[0] = 0;
      for (int s = 0; s < 256; s++) cm[s + 1] = cm[s] + fr[s];
    }
    if (off + 16 > blen) return -4;
    uint32_t states[4];
    for (int j = 0; j < 4; j++) {
      std::memcpy(&states[j], body + off, 4);
      off += 4;
    }
    int64_t q = (int64_t)raw_size / 4;
    int64_t pos[4] = {0, q, 2 * q, 3 * q};
    int64_t ends[4] = {q, 2 * q, 3 * q, (int64_t)raw_size};
    int ctx[4] = {0, 0, 0, 0};
    int64_t remaining = raw_size;
    while (remaining) {
      for (int j = 0; j < 4; j++) {
        if (pos[j] >= ends[j]) continue;
        int c = ctx[j];
        if (!built[c]) {
          const int64_t* cm = &cum[(int64_t)c * 257];
          if (cm[256] != kTotFreq) return -6;
          uint8_t* lk = &lookups[(int64_t)c * kTotFreq];
          for (int s = 0; s < 256; s++)
            for (int64_t k = cm[s]; k < cm[s + 1]; k++) lk[k] = (uint8_t)s;
          built[c] = true;
        }
        uint32_t x = states[j];
        uint32_t m = x & (kTotFreq - 1);
        int s = lookups[(int64_t)c * kTotFreq + m];
        out[pos[j]] = (uint8_t)s;
        x = (uint32_t)freqs[(int64_t)c * 256 + s] * (x >> kTfShift) + m -
            (uint32_t)cum[(int64_t)c * 257 + s];
        while (x < kRansLow) {
          if (off >= blen) return -8;
          x = (x << 8) | body[off++];
        }
        states[j] = x;
        ctx[j] = s;
        pos[j]++;
        remaining--;
      }
    }
    return 0;
  }
  return -7;
}

// Ragged segment gather: for each i, copy segment indices[i] of
// (flat, offsets) to out at new_off[i] (both in elements of size
// `elem` bytes). The caller computes new_off as the cumsum of gathered
// lengths; per-segment memcpy beats numpy's repeat/arange/fancy-index
// construction ~10x on the sort permute path (bam/columnar.py).
//
// The offsets table is validated BEFORE the memcpy loop: a
// non-monotone entry would compute a negative length that casts to a
// huge size_t (an OOB copy), and an offsets[-1] past the flat buffer
// would read beyond it. Returns 0 on success, -1 for an index out of
// [0, nseg), -2 for a negative/non-monotone offsets table, -3 when
// offsets overrun flat_elems.
int64_t disq_segment_gather(const uint8_t* flat, int64_t flat_elems,
                            const int64_t* offsets, int64_t nseg,
                            const int64_t* indices, int64_t n,
                            const int64_t* new_off, uint8_t* out,
                            int64_t elem) {
  if (nseg < 0 || (nseg >= 0 && offsets[0] < 0)) return -2;
  for (int64_t s = 0; s < nseg; s++)
    if (offsets[s + 1] < offsets[s]) return -2;
  if (offsets[nseg] > flat_elems) return -3;
  for (int64_t i = 0; i < n; i++) {
    int64_t s = indices[i];
    if (s < 0 || s >= nseg) return -1;
    int64_t len = (offsets[s + 1] - offsets[s]) * elem;
    if (len)
      memcpy(out + new_off[i] * elem, flat + offsets[s] * elem,
             (size_t)len);
  }
  return 0;
}

}  // extern "C"
