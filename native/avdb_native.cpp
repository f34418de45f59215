// avdb_native: host-side ingest runtime for the TPU variant-annotation
// framework.
//
// The reference's ingest is a per-line Python VcfEntryParser
// (Util/lib/python/parsers/vcf_parser.py:76-231) feeding a per-variant hot
// loop; its only "native" ingest is mmap + gzip (load_vcf_file.py:99-102).
// Here the tokenizer itself is native: it scans a decompressed text chunk,
// expands multi-allelic sites, and writes the device-ready columnar batch
// (chromosome codes, positions, width-bounded allele bytes + true lengths)
// straight into caller-provided numpy buffers — no per-row Python objects.
//
// Contract (mirrors annotatedvdb_tpu/io/vcf.py VcfBatchReader):
//   - lines starting '#' and blank lines are skipped;
//   - CHROM strips a "chr" prefix, "MT" folds to "M"; codes are 1..22,
//     X=23, Y=24, M=25; code 0 (unplaceable contig) skips the line and
//     counts skipped_contig;
//   - ALT splits on ','; a "." alt is skipped and counts skipped_alt;
//   - only COMPLETE lines are consumed (a multi-allelic site never
//     straddles chunks); the caller re-feeds the unconsumed tail;
//   - string-typed columns (ID, INFO, QUAL/FILTER/FORMAT, REF/ALT over the
//     device width) come back as (offset, length) spans into the caller's
//     buffer so Python materializes only what it needs.
//
// Build: g++ -O3 -shared -fPIC (see annotatedvdb_tpu/native/__init__.py).

#include <cstdint>
#include <cstring>

namespace {

inline int8_t chrom_code(const char* s, int len) {
    if (len >= 3 && s[0] == 'c' && s[1] == 'h' && s[2] == 'r') {
        s += 3;
        len -= 3;
    }
    if (len == 1) {
        switch (s[0]) {
            case 'X': return 23;
            case 'Y': return 24;
            case 'M': return 25;
            default: break;
        }
        if (s[0] >= '1' && s[0] <= '9') return static_cast<int8_t>(s[0] - '0');
        return 0;
    }
    if (len == 2) {
        if (s[0] == 'M' && s[1] == 'T') return 25;
        if (s[0] >= '1' && s[0] <= '2' && s[1] >= '0' && s[1] <= '9') {
            int v = (s[0] - '0') * 10 + (s[1] - '0');
            if (v >= 10 && v <= 22) return static_cast<int8_t>(v);
        }
    }
    return 0;
}

// parse a non-negative decimal; returns -1 on any non-digit byte
inline int64_t parse_pos(const char* s, int len) {
    if (len <= 0) return -1;
    int64_t v = 0;
    for (int i = 0; i < len; ++i) {
        char c = s[i];
        if (c < '0' || c > '9') return -1;
        v = v * 10 + (c - '0');
        if (v > INT64_C(0x7fffffff)) return -1;
    }
    return v;
}

struct Span {
    const char* ptr;
    int len;
};

inline bool is_space(char c) {
    return c == ' ' || c == '\t' || c == '\n' || c == '\r'
        || c == '\v' || c == '\f';
}

// 4-bit allele codes for nibble-packed device uploads; 0 = pad byte,
// 255 = unpackable.  MUST match _ALPHABET in annotatedvdb_tpu/ops/pack.py.
struct NibbleLut {
    uint8_t enc[256];
    NibbleLut() {
        memset(enc, 255, sizeof(enc));
        enc[0] = 0;
        const char* alphabet = "ACGTNacgtn*.-";
        for (int i = 0; alphabet[i]; ++i)
            enc[static_cast<uint8_t>(alphabet[i])] =
                static_cast<uint8_t>(i + 1);
    }
};
const NibbleLut kNibble;

// pack one width-w byte row into ceil(w/2) nibble pairs; returns false on
// any out-of-alphabet byte (row left undefined, caller uploads raw bytes)
inline bool pack_row(const uint8_t* src, int width, uint8_t* dst) {
    int cols = (width + 1) / 2;
    for (int k = 0; k < cols; ++k) {
        uint8_t lo = kNibble.enc[src[2 * k]];
        uint8_t hi = (2 * k + 1 < width) ? kNibble.enc[src[2 * k + 1]] : 0;
        if (lo == 255 || hi == 255) return false;
        dst[k] = static_cast<uint8_t>(lo | (hi << 4));
    }
    return true;
}

// FNV-1a over (ref_len&0xFF, alt_len&0xFF, padded ref row, padded alt row):
// the bit-exact twin of ops/hashing.py::allele_hash over the width-bounded
// device arrays.  Zero pad bytes fold to h *= prime^pad (x ^ 0 == x), so the
// caller passes a prime-power table and content bytes are the only loop.
inline uint32_t pad_fold(uint32_t h, int pad, const uint32_t* pp, int pp_n) {
    while (pad >= pp_n) {  // widths beyond the table: fold in steps
        h *= pp[pp_n - 1];
        pad -= pp_n - 1;
    }
    return h * pp[pad];
}

inline uint32_t fnv_row(const uint8_t* ref_row, const uint8_t* alt_row,
                        int width, int32_t rl, int32_t al,
                        const uint32_t* primepow, int pp_n) {
    uint32_t h = 2166136261u;
    const uint32_t prime = 16777619u;
    h = (h ^ static_cast<uint32_t>(rl & 0xFF)) * prime;
    h = (h ^ static_cast<uint32_t>(al & 0xFF)) * prime;
    int rc = rl < width ? rl : width;
    for (int i = 0; i < rc; ++i) h = (h ^ ref_row[i]) * prime;
    h = pad_fold(h, width - rc, primepow, pp_n);
    int ac = al < width ? al : width;
    for (int i = 0; i < ac; ++i) h = (h ^ alt_row[i]) * prime;
    h = pad_fold(h, width - ac, primepow, pp_n);
    return h;
}

// refsnp number for one site: ID "rs<digits>" wins, else INFO "RS=<digits>"
// (key-anchored: start of INFO or after ';'), else -1.  Mirrors the Python
// reader's ref_snp derivation + loaders' _rs_number parse so the insert path
// never materializes the ID string.  *weird is set when the row HAS a
// refsnp string (ID containing 'rs', or an INFO RS entry) that does not
// parse to a number — the rare rows whose primary keys must fall back to
// the materialized string.
inline int64_t rs_number_of(const Span& id, const Span& info, bool has_info,
                            uint8_t* weird) {
    *weird = 0;
    if (id.len > 2 && id.ptr[0] == 'r' && id.ptr[1] == 's') {
        int64_t v = 0;
        bool ok = true;
        for (int i = 2; i < id.len && ok; ++i) {
            char c = id.ptr[i];
            if (c < '0' || c > '9') ok = false;
            else if (v > (INT64_MAX - 9) / 10) ok = false;  // int64 bound
            else v = v * 10 + (c - '0');
        }
        if (ok) {
            // zero-padded ids ("rs0012") round-trip through the int as
            // "rs12": flag them so PKs use the verbatim string
            if (id.len > 3 && id.ptr[2] == '0') *weird = 1;
            return v;
        }
    }
    // an ID containing 'rs' anywhere IS the refsnp string (reference
    // substring rule, vcf_parser.py:158-169) — it shadows INFO RS even when
    // it does not parse to a number
    for (int i = 0; i + 1 < id.len; ++i)
        if (id.ptr[i] == 'r' && id.ptr[i + 1] == 's') {
            *weird = 1;
            return -1;
        }
    if (!has_info) return -1;
    // the Python chain routes the RS value through int() then re-prints it
    // ("rs" + str(int(v))), so mirror int()'s accepted forms: optional '+'
    // and single underscores BETWEEN digits; last RS= key wins (dict
    // assignment order in parse_info)
    const char* s = info.ptr;
    int64_t result = -1;
    for (int i = 0; i + 3 <= info.len; ++i) {
        if ((i == 0 || s[i - 1] == ';')
            && s[i] == 'R' && s[i + 1] == 'S' && s[i + 2] == '=') {
            int64_t v = 0;
            bool ok = false, prev_digit = false;
            int j = i + 3;
            // int() strips surrounding ASCII whitespace
            while (j < info.len && is_space(s[j])) ++j;
            if (j < info.len && s[j] == '+') ++j;
            for (; j < info.len && s[j] != ';'; ++j) {
                char c = s[j];
                if (c >= '0' && c <= '9') {
                    if (v > (INT64_MAX - 9) / 10) {  // int64 bound
                        ok = false;
                        break;
                    }
                    v = v * 10 + (c - '0');
                    ok = prev_digit = true;
                } else if (c == '_' && prev_digit) {
                    prev_digit = false;  // int() wants digits on both sides
                } else if (is_space(c) && ok && prev_digit) {
                    // trailing whitespace only: anything after must be
                    // whitespace until ';' or end
                    for (; j < info.len && s[j] != ';'; ++j)
                        if (!is_space(s[j])) { ok = false; break; }
                    break;
                } else {
                    ok = false;
                    break;
                }
            }
            result = (ok && prev_digit) ? v : -1;
            // an RS entry that fails int() still yields a "rs<value>"
            // string in the Python chain — flag it (cleared by a later
            // parsable RS key, matching last-key-wins)
            *weird = result < 0 ? 1 : 0;
        }
    }
    return result;
}


// bytes a mapping line may carry verbatim inside a JSON string: printable
// ASCII less '"' and '\\' — the loader's no-escaping-needed check
// (isascii + isprintable + the two quote tests), as a table
struct PlainLut {
    uint8_t ok[256];
    PlainLut() {
        memset(ok, 0, sizeof(ok));
        for (int c = 0x20; c < 0x7f; ++c) ok[c] = 1;
        ok[static_cast<uint8_t>('"')] = 0;
        ok[static_cast<uint8_t>('\\')] = 0;
    }
};
const PlainLut kPlain;

// an allele cell the scalar route would read as the same string: len plain
// bytes, then zero padding to the width (decode_alleles reads the cell up
// to its last non-zero byte, not up to len)
inline bool plain_allele(const uint8_t* s, int len, int width) {
    uint8_t ok = 1, pad = 0;
    for (int i = 0; i < len; ++i) ok &= kPlain.ok[s[i]];
    for (int i = len; i < width; ++i) pad |= s[i];
    return ok != 0 && pad == 0;
}

inline uint8_t* put_bytes(uint8_t* p, const void* s, int64_t len) {
    memcpy(p, s, static_cast<size_t>(len));
    return p + len;
}

inline uint8_t* put_uint(uint8_t* p, uint64_t v) {
    uint8_t tmp[20];
    int k = 0;
    do {
        tmp[k++] = static_cast<uint8_t>('0' + v % 10);
        v /= 10;
    } while (v);
    while (k) *p++ = tmp[--k];
    return p;
}

// chr:pos:ref:alt — the metaseq id of io/egress.py metaseq_ids, for a row
// that passed avdb_mapping_fast_rows (code 1..25, pos >= 0, alleles within
// the width)
inline uint8_t* put_metaseq(uint8_t* p, int8_t code, int32_t pos,
                            const uint8_t* ref, int32_t rl,
                            const uint8_t* alt, int32_t al) {
    if (code <= 22) p = put_uint(p, static_cast<uint64_t>(code));
    else *p++ = static_cast<uint8_t>("XYM"[code - 23]);
    *p++ = ':';
    p = put_uint(p, static_cast<uint64_t>(pos));
    *p++ = ':';
    p = put_bytes(p, ref, rl);
    *p++ = ':';
    return put_bytes(p, alt, al);
}

#define AVDB_LIT(p, s) put_bytes((p), (s), sizeof(s) - 1)

}  // namespace

extern "C" {

// Counters layout (int64):
//   [0] lines parsed (data lines seen, valid or not)
//   [1] skipped_contig
//   [2] skipped_alt
//   [3] malformed (fewer than 5 columns or bad POS)
//   [4] TOTAL lines consumed (headers/blank included) — the caller's
//       absolute line_base advance, so it never re-scans the window for
//       newlines
//
// Returns the number of rows written.  *consumed is the byte count of fully
// processed lines; *need_more is set to 1 when the row buffers filled up
// before the chunk was exhausted (caller flushes and re-feeds from
// *consumed).
int64_t avdb_parse_vcf_chunk(
    const char* buf, int64_t n_bytes, int32_t width, int64_t max_rows,
    int64_t line_base,
    // per-row outputs (device batch)
    int8_t* chrom, int32_t* pos, uint8_t* ref, uint8_t* alt,
    int32_t* ref_len, int32_t* alt_len, uint8_t* multi,
    int64_t* line_no,
    // per-row spans into buf (host sidecar, lazily materialized)
    int64_t* ref_off, int64_t* alt_off,
    int64_t* id_off, int32_t* id_len,
    int64_t* qual_off, int32_t* qual_len,
    int64_t* filter_off, int32_t* filter_len,
    int64_t* info_off, int32_t* info_len,
    int64_t* format_off, int32_t* format_len,
    // full ALT column span (multi-allelic variant ids need it verbatim)
    int64_t* altcol_off, int32_t* altcol_len,
    // site index of each row within its line (alt ordinal) + alt count
    int32_t* alt_index, int32_t* n_alts_out,
    // refsnp number (ID "rs<digits>", else INFO RS=, else -1) + per-row
    // flag for rows whose refsnp STRING exists but does not parse (their
    // primary keys need the materialized string); identity_only loads skip
    // the INFO fallback, mirroring the readers' skipped INFO parse
    int64_t* rs_number, uint8_t* rs_weird,
    // 1 when the ID column is a verbatim variant id (not '.' and not an
    // rs accession) — those rows' mapping ids must use the ID string;
    // all others use the assembled chr:pos:ref:altcol form
    uint8_t* id_verbatim,
    // 1 when INFO carries a key-anchored FREQ= entry (the insert path reads
    // the frequencies column for every row; this flag lets it skip the lazy
    // INFO parse wholesale on FREQ-less rows/chunks)
    uint8_t* has_freq,
    // uint32 FNV-1a allele-identity hash per row (ops/hashing.py twin over
    // the width-bounded arrays) — computed during the scan while the allele
    // bytes are cache-hot, so host paths never pay a device hash round trip
    uint32_t* hash_out,
    // nibble-packed allele uploads: [cap, ceil(width/2)] each + per-row
    // packable flag (0 when the row holds out-of-alphabet bytes).
    // want_packed=0 skips the pack work entirely (consumers that never
    // upload, e.g. mesh-path loads and export scans)
    uint8_t* ref_packed, uint8_t* alt_packed, uint8_t* pack_ok,
    int32_t identity_only, int32_t want_packed,
    int64_t* counters, int64_t* consumed, int32_t* need_more) {
    int64_t rows = 0;
    int64_t offset = 0;
    int64_t line = line_base;
    *need_more = 0;

    // prime^k table for zero-pad folding in fnv_row (k in [0, width])
    uint32_t primepow_buf[4096];
    int pp_n = width + 1 <= 4096 ? width + 1 : 4096;
    primepow_buf[0] = 1u;
    for (int k = 1; k < pp_n; ++k)
        primepow_buf[k] = primepow_buf[k - 1] * 16777619u;

    while (offset < n_bytes) {
        const char* nl = static_cast<const char*>(
            memchr(buf + offset, '\n', static_cast<size_t>(n_bytes - offset)));
        if (nl == nullptr) break;  // incomplete final line: leave for caller
        const char* p = buf + offset;
        int64_t len = nl - p;
        int64_t next_offset = offset + len + 1;
        ++line;

        if (len == 0 || p[0] == '#') {
            offset = next_offset;
            continue;
        }
        // strip a trailing '\r' (CRLF VCFs)
        if (len > 0 && p[len - 1] == '\r') --len;
        bool blank = true;
        for (int64_t i = 0; i < len && blank; ++i)
            blank = (p[i] == ' ' || p[i] == '\t');
        if (blank) {
            offset = next_offset;
            continue;
        }
        counters[0]++;

        // tokenize up to 9 tab-separated fields (memchr: the per-byte scan
        // was the tokenizer's single largest cost on long INFO columns)
        Span fields[9];
        int nf = 0;
        const char* start = p;
        const char* end = p + len;
        while (nf < 9) {
            const char* tab = static_cast<const char*>(
                memchr(start, '\t', static_cast<size_t>(end - start)));
            const char* stop = tab ? tab : end;
            fields[nf].ptr = start;
            fields[nf].len = static_cast<int>(stop - start);
            ++nf;
            if (tab == nullptr) break;
            start = tab + 1;
        }
        if (nf < 5) {
            counters[3]++;
            offset = next_offset;
            continue;
        }
        int8_t code = chrom_code(fields[0].ptr, fields[0].len);
        if (code == 0) {
            counters[1]++;
            offset = next_offset;
            continue;
        }
        int64_t position = parse_pos(fields[1].ptr, fields[1].len);
        if (position < 0) {
            counters[3]++;
            offset = next_offset;
            continue;
        }

        // count alts for capacity + multi-allelic flag
        int n_alts = 1;
        for (int i = 0; i < fields[4].len; ++i)
            if (fields[4].ptr[i] == ',') ++n_alts;
        if (rows + n_alts > max_rows) {
            counters[0]--;  // the line is re-fed (and re-counted) next call
            --line;         // ... and is NOT consumed this call
            *need_more = 1;
            break;  // line does not fit: flush and re-feed
        }

        const Span& id_f = fields[2];  // ID
        const Span& rr = fields[3];    // REF
        bool has_qual = nf > 5 && !(fields[5].len == 1 && fields[5].ptr[0] == '.');
        bool has_filter = nf > 6 && !(fields[6].len == 1 && fields[6].ptr[0] == '.');
        bool has_info = nf > 7 && !(fields[7].len == 1 && fields[7].ptr[0] == '.');
        bool has_format = nf > 8 && !(fields[8].len == 1 && fields[8].ptr[0] == '.');

        uint8_t rs_w = 0;
        int64_t rs = rs_number_of(
            id_f, fields[7], has_info && !identity_only, &rs_w);
        uint8_t id_verb =
            !(id_f.len == 1 && id_f.ptr[0] == '.')
            && !(id_f.len >= 2 && id_f.ptr[0] == 'r' && id_f.ptr[1] == 's')
            ? 1 : 0;
        uint8_t freq_flag = 0;
        if (has_info && !identity_only) {
            const char* s = fields[7].ptr;
            for (int i = 0; i + 5 <= fields[7].len; ++i) {
                if ((i == 0 || s[i - 1] == ';')
                    && s[i] == 'F' && s[i + 1] == 'R' && s[i + 2] == 'E'
                    && s[i + 3] == 'Q' && s[i + 4] == '=') {
                    freq_flag = 1;
                    break;
                }
            }
        }

        const char* alt_start = fields[4].ptr;
        const char* alt_end = fields[4].ptr + fields[4].len;
        int ordinal = 0;
        for (const char* q = alt_start; q <= alt_end; ++q) {
            if (q == alt_end || *q == ',') {
                int alen = static_cast<int>(q - alt_start);
                ++ordinal;
                if (alen == 1 && alt_start[0] == '.') {
                    counters[2]++;
                } else {
                    int64_t r = rows++;
                    chrom[r] = code;
                    pos[r] = static_cast<int32_t>(position);
                    ref_len[r] = rr.len;
                    alt_len[r] = alen;
                    int rcopy = rr.len < width ? rr.len : width;
                    int acopy = alen < width ? alen : width;
                    memcpy(ref + r * width, rr.ptr, static_cast<size_t>(rcopy));
                    if (rcopy < width)
                        memset(ref + r * width + rcopy, 0,
                               static_cast<size_t>(width - rcopy));
                    memcpy(alt + r * width, alt_start, static_cast<size_t>(acopy));
                    if (acopy < width)
                        memset(alt + r * width + acopy, 0,
                               static_cast<size_t>(width - acopy));
                    multi[r] = n_alts > 1 ? 1 : 0;
                    line_no[r] = line;
                    ref_off[r] = rr.ptr - buf;
                    alt_off[r] = alt_start - buf;
                    id_off[r] = id_f.ptr - buf;
                    id_len[r] = id_f.len;
                    qual_off[r] = has_qual ? fields[5].ptr - buf : -1;
                    qual_len[r] = has_qual ? fields[5].len : 0;
                    filter_off[r] = has_filter ? fields[6].ptr - buf : -1;
                    filter_len[r] = has_filter ? fields[6].len : 0;
                    info_off[r] = has_info ? fields[7].ptr - buf : -1;
                    info_len[r] = has_info ? fields[7].len : 0;
                    format_off[r] = has_format ? fields[8].ptr - buf : -1;
                    format_len[r] = has_format ? fields[8].len : 0;
                    altcol_off[r] = fields[4].ptr - buf;
                    altcol_len[r] = fields[4].len;
                    alt_index[r] = ordinal - 1;
                    n_alts_out[r] = n_alts;
                    rs_number[r] = rs;
                    rs_weird[r] = rs_w;
                    id_verbatim[r] = id_verb;
                    has_freq[r] = freq_flag;
                    hash_out[r] = fnv_row(
                        ref + r * width, alt + r * width, width,
                        ref_len[r], alt_len[r], primepow_buf, pp_n);
                    if (want_packed) {
                        int cols = (width + 1) / 2;
                        bool ok = pack_row(ref + r * width, width,
                                           ref_packed + r * cols)
                               && pack_row(alt + r * width, width,
                                           alt_packed + r * cols);
                        pack_ok[r] = ok ? 1 : 0;
                    } else {
                        pack_ok[r] = 0;
                    }
                }
                alt_start = q + 1;
            }
        }
        offset = next_offset;
        // NOTE: rr.len (REF) is written in full to ref_len even when it
        // exceeds width — the device flags such rows host_fallback, exactly
        // like the Python reader.
    }
    counters[4] = line - line_base;
    *consumed = offset;
    return rows;
}

// ---- the load's mapping sidecar, written from the chunk's columns ----
//
// One line a row, exactly the loader's (loaders/vcf_loader.py, the scalar
// route of io/egress.py mapping_lines):
//   {"<id>": [{"primary_key": "<id>[:rs<N>]", "bin_index": "<path>"}]}\n
// with <id> = chr:pos:ref:alt.  Rows whose line is not that function of
// the columns (verbatim ids, multi-allelic sites, digest keys, ...) are
// the caller's: it renders them and hands their lines in.

// Clears fast[i] for every row this file cannot write from the columns
// alone: a chromosome code outside 1..25, a negative position, an allele
// longer than the width (or of negative length), an allele byte that a
// JSON string cannot carry verbatim, or a cell not zero-padded past its
// length.  The caller has already cleared the
// rows its flag columns rule out.  Returns the rows still set.
int64_t avdb_mapping_fast_rows(
    int64_t n, int32_t width,
    const int8_t* chrom, const int32_t* pos,
    const uint8_t* ref, const uint8_t* alt,
    const int32_t* ref_len, const int32_t* alt_len,
    uint8_t* fast) {
    int64_t kept = 0;
    for (int64_t i = 0; i < n; ++i) {
        if (!fast[i]) continue;
        int32_t rl = ref_len[i], al = alt_len[i];
        bool ok = chrom[i] >= 1 && chrom[i] <= 25 && pos[i] >= 0
            && rl >= 0 && rl <= width && al >= 0 && al <= width
            && plain_allele(ref + i * width, rl, width)
            && plain_allele(alt + i * width, al, width);
        fast[i] = ok ? 1 : 0;
        kept += ok;
    }
    return kept;
}

// Writes the n rows' lines into out in row order: a fast row's from the
// columns, any other row's copied from the caller's rendered lines
// (slow_bytes, cut at slow_end[k] for the k-th row that is not fast, each
// with its newline).  path_idx[i] indexes the chunk's table of distinct
// bin paths: entry t is path_bytes[path_off[t] .. path_off[t + 1]).
// Returns the bytes written, -1 if out_cap would not hold them (nothing
// past out_cap is touched), or -2 if a row marked fast is not one
// avdb_mapping_fast_rows would keep or indexes no path of the table.
int64_t avdb_mapping_lines(
    int64_t n, int32_t width,
    const int8_t* chrom, const int32_t* pos,
    const uint8_t* ref, const uint8_t* alt,
    const int32_t* ref_len, const int32_t* alt_len,
    const int64_t* rs_number,
    const int64_t* path_idx, const uint8_t* path_bytes,
    const int64_t* path_off, int64_t n_paths,
    const uint8_t* fast,
    const uint8_t* slow_bytes, const int64_t* slow_end,
    uint8_t* out, int64_t out_cap) {
    uint8_t* p = out;
    uint8_t* const cap = out + out_cap;
    int64_t k = 0, slow_at = 0;
    for (int64_t i = 0; i < n; ++i) {
        if (!fast[i]) {
            int64_t len = slow_end[k++] - slow_at;
            if (len > cap - p) return -1;
            p = put_bytes(p, slow_bytes + slow_at, len);
            slow_at += len;
            continue;
        }
        int32_t rl = ref_len[i], al = alt_len[i];
        if (chrom[i] < 1 || chrom[i] > 25 || pos[i] < 0 || rl < 0
            || rl > width || al < 0 || al > width || path_idx[i] < 0
            || path_idx[i] >= n_paths)
            return -2;
        int64_t at = path_off[path_idx[i]];
        int64_t path_len = path_off[path_idx[i] + 1] - at;
        // 45 bytes of punctuation, two ids of at most 2+10+3 bytes beside
        // their alleles, ":rs" and at most 19 digits
        if (45 + 2 * (15 + int64_t(rl) + al) + 22 + path_len > cap - p)
            return -1;
        const uint8_t* r = ref + i * width;
        const uint8_t* a = alt + i * width;
        p = AVDB_LIT(p, "{\"");
        const uint8_t* id = p;
        p = put_metaseq(p, chrom[i], pos[i], r, rl, a, al);
        int64_t id_len = p - id;
        p = AVDB_LIT(p, "\": [{\"primary_key\": \"");
        p = put_bytes(p, id, id_len);
        if (rs_number[i] >= 0) {
            p = AVDB_LIT(p, ":rs");
            p = put_uint(p, static_cast<uint64_t>(rs_number[i]));
        }
        p = AVDB_LIT(p, "\", \"bin_index\": \"");
        p = put_bytes(p, path_bytes + at, path_len);
        p = AVDB_LIT(p, "\"}]}\n");
    }
    return p - out;
}

// ---- a lookup group's identity columns, from its allele strings ----
//
// One pass over n (ref, alt) pairs given as the group's ref strings joined
// with no padding (ref_bytes, row i's ref_len[i] bytes after row i-1's) and
// the same for the alts: the columns of types.py encode_allele_array twice
// and loaders/lookup.py identity_hashes over ASCII strings.  ref and alt
// ([n, width] each) get the row's bytes truncated at the width and zero
// padded; h gets fnv_row, or for a row with an allele longer than the
// width the full-string FNV of vcf_loader.py _fnv32_str (the length bytes,
// then every ref byte, then every alt byte).  primepow[k] is prime^k for k
// in [0, pp_n).  Returns 0, or -1 (nothing written) if a length is
// negative or the lengths do not add up to ref_total / alt_total.
int64_t avdb_identity_columns(
    const uint8_t* ref_bytes, const int32_t* ref_len, int64_t ref_total,
    const uint8_t* alt_bytes, const int32_t* alt_len, int64_t alt_total,
    int64_t n, int32_t width, const uint32_t* primepow, int32_t pp_n,
    uint8_t* ref, uint8_t* alt, uint32_t* h) {
    int64_t rsum = 0, asum = 0;
    for (int64_t i = 0; i < n; ++i) {
        if (ref_len[i] < 0 || alt_len[i] < 0) return -1;
        rsum += ref_len[i];
        asum += alt_len[i];
    }
    if (rsum != ref_total || asum != alt_total) return -1;
    const uint32_t prime = 16777619u;
    const uint8_t* rs = ref_bytes;
    const uint8_t* as = alt_bytes;
    for (int64_t i = 0; i < n; ++i) {
        int32_t rl = ref_len[i], al = alt_len[i];
        uint8_t* rrow = ref + i * width;
        uint8_t* arow = alt + i * width;
        int rc = rl < width ? rl : width;
        int ac = al < width ? al : width;
        memcpy(rrow, rs, static_cast<size_t>(rc));
        memset(rrow + rc, 0, static_cast<size_t>(width - rc));
        memcpy(arow, as, static_cast<size_t>(ac));
        memset(arow + ac, 0, static_cast<size_t>(width - ac));
        if (rl > width || al > width) {
            uint32_t v = 2166136261u;
            v = (v ^ static_cast<uint32_t>(rl & 0xFF)) * prime;
            v = (v ^ static_cast<uint32_t>(al & 0xFF)) * prime;
            for (int32_t k = 0; k < rl; ++k) v = (v ^ rs[k]) * prime;
            for (int32_t k = 0; k < al; ++k) v = (v ^ as[k]) * prime;
            h[i] = v;
        } else {
            h[i] = fnv_row(rrow, arow, width, rl, al, primepow, pp_n);
        }
        rs += rl;
        as += al;
    }
    return 0;
}

}  // extern "C"

// ---- a FREQ-bearing row's frequency sidecar, from its INFO span ----
//
// One row's value is io/vcf.py freq_sidecar(info, n_alts)[alt_index].text:
//   {"<pop>": {"gmaf": <number>}, ...}
// over the populations of INFO's last FREQ= entry that hold a value (not
// "." or "0") at slot alt_index + 1.  A row is written only where these
// bytes are provably Python's; every other row is declined, and the
// caller takes it through freq_sidecar itself.

namespace {

// a population name json.dumps renders verbatim between quotes, within
// io/vcf.py _FREQ_KEY_RE (':' and '|' cannot occur: they cut the name)
struct FreqKeyLut {
    uint8_t ok[256] = {};
    FreqKeyLut() {
        for (int c = '0'; c <= '9'; ++c) ok[c] = 1;
        for (int c = 'A'; c <= 'Z'; ++c) ok[c] = 1;
        for (int c = 'a'; c <= 'z'; ++c) ok[c] = 1;
        for (const char* c = " _.,/-"; *c; ++c)
            ok[static_cast<uint8_t>(*c)] = 1;
    }
};
const FreqKeyLut kFreqKey;

inline bool is_digit(char c) { return c >= '0' && c <= '9'; }

// The number Python writes for a FREQ value v: str(int(v)) for an
// integer, repr(float(v)) for a plain decimal of at most 15 significant
// digits in [1e-4, 1e16) or of value zero — DBL_DIG is 15, so such a
// decimal is the shortest string that round-trips, and repr prints it in
// positional form with its trailing zeros trimmed to one place.  Returns
// the end of what was written, or nullptr for any other form (exponents,
// inf/nan, more digits, whitespace, ...), writing nothing then.
inline uint8_t* put_gmaf(uint8_t* p, const char* v, int len) {
    int i = 0;
    bool neg = false;
    if (len > 0 && (v[0] == '+' || v[0] == '-')) {
        neg = v[0] == '-';
        i = 1;
    }
    int dot = -1;
    for (int k = i; k < len; ++k) {
        if (v[k] == '.' && dot < 0) dot = k;
        else if (!is_digit(v[k])) return nullptr;
    }
    if (dot < 0) {  // an integer: sign and leading zeros fall away
        if (len == i || len - i > 1000) return nullptr;  // int()'s 4300 cap
        int b = i;
        while (b < len && v[b] == '0') ++b;
        if (b == len) {
            *p++ = '0';
            return p;
        }
        if (neg) *p++ = '-';
        return put_bytes(p, v + b, len - b);
    }
    int ib = i, ie = dot, fb = dot + 1, fe = len;
    if (ib == ie && fb == fe) return nullptr;  // a lone sign and dot
    while (ib < ie && v[ib] == '0') ++ib;      // leading zeros
    while (fe > fb && v[fe - 1] == '0') --fe;  // trailing zeros
    if (ib == ie && fe == fb) {                // zero: 0.0 / -0.0
        if (neg) *p++ = '-';
        return AVDB_LIT(p, "0.0");
    }
    int sig;
    if (ib < ie) {  // |v| >= 1
        if (ie - ib > 16) return nullptr;  // >= 1e16: repr takes exponents
        int last = ie;
        if (fe == fb)
            while (v[last - 1] == '0') --last;  // 1200. has 2 digits
        sig = (last - ib) + (fe - fb);
    } else {  // |v| < 1
        int nz = fb;
        while (v[nz] == '0') ++nz;
        if (nz - fb > 3) return nullptr;  // < 1e-4: repr takes exponents
        sig = fe - nz;
    }
    if (sig > 15) return nullptr;
    if (neg) *p++ = '-';
    if (ib < ie) p = put_bytes(p, v + ib, ie - ib);
    else *p++ = '0';
    *p++ = '.';
    if (fe > fb) return put_bytes(p, v + fb, fe - fb);
    *p++ = '0';
    return p;
}

struct Cut {
    const char* ptr;
    int len;
};

enum FreqOutcome : uint8_t { kFreqNone = 0, kFreqWritten = 1, kFreqDeclined = 2 };

constexpr int kMaxPops = 64;

// One row: writes its text and a newline at p and returns kFreqWritten,
// or returns kFreqNone / kFreqDeclined and leaves *end at p.
inline uint8_t freq_row(const char* s, int len, int32_t n_alts,
                        int32_t alt_index, uint8_t* p, uint8_t** end) {
    *end = p;
    if (len <= 0) return kFreqNone;
    // freq_sidecar scrubs \x2c, \x59 and '#' before it splits: declined
    if (memchr(s, '\\', len) || memchr(s, '#', len)) return kFreqDeclined;
    if (alt_index < 0 || alt_index >= n_alts) return kFreqDeclined;
    // the last ';'-item that starts FREQ= (dict semantics: last wins)
    const char* raw = nullptr;
    const char* raw_end = nullptr;
    for (const char* item = s; item <= s + len;) {
        const char* semi = static_cast<const char*>(
            memchr(item, ';', static_cast<size_t>(s + len - item)));
        const char* stop = semi ? semi : s + len;
        if (stop - item >= 5 && memcmp(item, "FREQ=", 5) == 0) {
            raw = item + 5;
            raw_end = stop;
        }
        item = stop + 1;
    }
    if (raw == nullptr) return kFreqNone;
    Cut names[kMaxPops];
    int n_names = 0;
    int slot = alt_index + 1;
    uint8_t* q = p;
    *q++ = '{';
    bool any = false;
    for (const char* pop = raw; pop <= raw_end;) {
        const char* bar = static_cast<const char*>(
            memchr(pop, '|', static_cast<size_t>(raw_end - pop)));
        const char* pop_end = bar ? bar : raw_end;
        const char* colon = static_cast<const char*>(
            memchr(pop, ':', static_cast<size_t>(pop_end - pop)));
        if (colon != nullptr) {  // a part without ':' is no population
            Cut name{pop, static_cast<int>(colon - pop)};
            if (name.len == 0 || n_names == kMaxPops) return kFreqDeclined;
            for (int k = 0; k < name.len; ++k)
                if (!kFreqKey.ok[static_cast<uint8_t>(name.ptr[k])])
                    return kFreqDeclined;
            for (int k = 0; k < n_names; ++k)  // repeated: first slot,
                if (names[k].len == name.len   // last value — declined
                    && memcmp(names[k].ptr, name.ptr, name.len) == 0)
                    return kFreqDeclined;
            names[n_names++] = name;
            // the slot-th ','-separated value, if there is one
            const char* v = colon + 1;
            for (int k = 0; k < slot && v != nullptr; ++k) {
                const char* comma = static_cast<const char*>(
                    memchr(v, ',', static_cast<size_t>(pop_end - v)));
                v = comma ? comma + 1 : nullptr;
            }
            if (v != nullptr) {
                const char* comma = static_cast<const char*>(
                    memchr(v, ',', static_cast<size_t>(pop_end - v)));
                int vlen = static_cast<int>((comma ? comma : pop_end) - v);
                bool skip = vlen == 1 && (v[0] == '.' || v[0] == '0');
                if (!skip) {
                    if (any) q = AVDB_LIT(q, ", ");
                    *q++ = '"';
                    q = put_bytes(q, name.ptr, name.len);
                    q = AVDB_LIT(q, "\": {\"gmaf\": ");
                    q = put_gmaf(q, v, vlen);
                    if (q == nullptr) return kFreqDeclined;
                    *q++ = '}';
                    any = true;
                }
            }
        }
        pop = pop_end + 1;
    }
    if (!any) return kFreqNone;
    q = AVDB_LIT(q, "}\n");
    *end = q;
    return kFreqWritten;
}

}  // namespace

extern "C" {

// For each of n rows — its INFO span buf[info_off[i] .. + info_len[i]),
// its line's alt count and its alt's ordinal among them — sets status[i]
// to 0 (no value: no FREQ entry, or no population with a value at the
// row's slot), 1 (written: the text and a newline appended to out) or 2
// (declined).  Returns the bytes written to out, or -1 if out_cap would
// not hold them: a row may take 3 + 18 * (info_len + 1) bytes — "{",
// "}\n", and for each population 17 beside its name and value, which
// grows by at most a byte (".5" -> 0.5).
int64_t avdb_freq_texts(
    const char* buf, int64_t n,
    const int64_t* info_off, const int32_t* info_len,
    const int32_t* n_alts, const int32_t* alt_index,
    uint8_t* status, uint8_t* out, int64_t out_cap) {
    uint8_t* p = out;
    uint8_t* const cap = out + out_cap;
    for (int64_t i = 0; i < n; ++i) {
        int32_t len = info_len[i];
        if (len > 0 && cap - p < 3 + 18 * (static_cast<int64_t>(len) + 1))
            return -1;
        status[i] = freq_row(len > 0 ? buf + info_off[i] : buf, len,
                             n_alts[i], alt_index[i], p, &p);
    }
    return p - out;
}

}  // extern "C"
