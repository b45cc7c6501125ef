"""Device-resident lossless encoding engine (jit/Pallas stage kernels).

Every numpy stage in this package is a *reference implementation*; this
module gives the hot ones a jit-compiled device twin with a **bit-identity
contract**: for the same input stream, ``<stage>_encode_device`` returns a
payload byte-for-byte equal to the numpy encoder's, so device-encoded
sections drop into existing containers (golden v1/v2/v3 fixtures included)
and a sharded writer and a single-host writer stay interchangeable.

The shape of each kernel follows the GPU literature the paper builds on
(cuSZ's two-phase Huffman, FZ-GPU's fused shuffle-and-encode):

* **hf** — frequencies come from :func:`histogram256_device` (the Pallas
  histogram256 kernel on TPU; on the host-backed CPU device a symbol-pair
  bincount over the same memory); the 256-leaf canonical codebook is
  O(256 log 256) scalar work and stays on host
  (:func:`repro.core.lossless.huffman.code_lengths`); emission is two
  fused jits: a pair-table gather + per-chunk exclusive prefix-sum bit
  offsets producing per-pair 32-bit word contributions, then a
  prefix-sum/boundary-gather reduction into the big-endian word stream —
  the same arithmetic as the numpy encoder, so the bitstream is
  identical.
* **rre/rze** — flag computation, MSB-first bitmap packing and the
  payload's exact size (kept rows and every bitmap level's non-zero
  bytes, fixed-shape reductions) run on device; a caller that would store
  the stage through learns so from those few scalars alone. Otherwise the
  kept-symbol compaction is a device row-gather addressed by the flag
  positions; only the packed bitmap (1/8k of the stream) crosses to host
  for the tiny recursive-bitmap recursion and header assembly.
* **bit1** — the plane shuffle runs through the Pallas bitshuffle kernel
  on TPU and a jnp twin elsewhere (identical bit layout either way).
* **tcms** — bytewise sign-magnitude bijection, one fused ``where``.

Inputs are taken as ``jax.Array`` uint8 streams and payloads are returned
as *device* uint8 arrays (plus the usual host header dict), so a pipeline
of device-capable stages chains without the stream ever visiting host —
:func:`repro.core.lossless.pipelines.encode` uses exactly that fast path.
Beyond encoded bytes, only flag bits (n/16 bytes for Huffman word
boundaries, n/8k for rre bitmaps) and O(1) scalars sync per stage —
XLA:CPU scatters run an order of magnitude behind its gathers, so the
staircase inversions those flags feed (``flatnonzero``) ride the host.

Compilation is keyed on padded shapes: streams are padded to the stage's
natural grid (Huffman chunks, 8192-symbol buckets for rre/rze/tcms,
shuffle blocks for bit1), so nearby lengths share a compiled kernel
instead of recompiling per byte count. Huffman additionally splits
>2^26-symbol streams into chunk-aligned slabs, keeping every bit cursor
inside u32 (the same slab trick — and the same byte-exact concatenation —
as the threaded numpy encoder).

Decode is symmetric: every encoder here has a ``<stage>_decode_device``
twin under the same bit-identity contract, so the read path
(:func:`repro.core.lossless.pipelines.decode` with ``device=True``, and
``Compressor.decompress`` above it) keeps the stream device-resident from
payload bytes to reconstructed field. Huffman decodes all chunks in
parallel by gathering against the per-chunk byte-offset table the encoder
emits into the section header (``"offs"``, a small versioned extension);
legacy headers without it — and any stream a twin can't handle — fall
back to the numpy reference decoder and re-upload, bit-identically.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..spans import to_device, to_host
from . import huffman as _hf
from . import rre as _rre

_U31 = jnp.uint32(31)
_SYM_PAD = 8192        # rre/rze/tcms row-padding granularity (bounds recompiles)
_SLAB_CHUNKS = 1 << 16  # 2^26 symbols per hf slab: bit cursors stay in u32
_BIT1_BLOCK = 8192      # host bitshuffle.BLOCK — the layout the payload pins


def is_device(x) -> bool:
    """True for jax device arrays (the fast-path trigger); numpy is host."""
    return isinstance(x, jax.Array) and not isinstance(x, np.ndarray)


def as_device_u8(x) -> jax.Array:
    """Flat uint8 device view of ``x`` (cast, like ``ascontiguousarray``).

    Accepts device arrays, numpy arrays, and raw bytes-like payloads
    (bytes / bytearray / memoryview) — the decode twins take whatever the
    pipeline stream hands them.
    """
    if isinstance(x, (bytes, bytearray, memoryview)):
        x = np.frombuffer(x, np.uint8)
    arr = x if is_device(x) else to_device(np.ascontiguousarray(x))
    if arr.dtype != jnp.uint8:
        arr = arr.astype(jnp.uint8)
    return arr.reshape(-1)


def _host_u8(x) -> np.ndarray:
    """Flat uint8 *host* view of a payload (zero-copy where possible)."""
    if is_device(x):
        return to_host(x, np.uint8).reshape(-1)
    if isinstance(x, np.ndarray):
        return np.ascontiguousarray(x).view(np.uint8).reshape(-1)
    return np.frombuffer(x, np.uint8)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------- histogram
def histogram256_device(data) -> np.ndarray:
    """Exact 256-bin counts of a uint8 stream (host ``np.int64``).

    Compiled on TPU this is the Pallas histogram256 kernel (one-hot
    contraction per tile); on the CPU backend, device memory IS host
    memory (``to_host`` is zero-copy), so the counts come from a
    symbol-PAIR ``np.bincount`` over the u16 view folded back to 256 bins
    — ~6x faster than a byte-wise bincount because it halves the element
    count fed through numpy's index conversion. Counts equal
    ``np.bincount`` exactly (they are integers), which is what keeps the
    orchestrator's pipeline choice identical between host and device
    paths.
    """
    d = as_device_u8(data)
    if _on_tpu():
        from repro.kernels.histogram.histogram import TILE, histogram256_raw

        pad = (-d.size) % TILE
        if pad:
            d = jnp.concatenate([d, jnp.zeros(pad, jnp.uint8)])
        hist = histogram256_raw(d, False)
        if pad:
            hist = hist.at[0].add(-pad)
        return to_host(hist, np.int64)
    dn = to_host(d)
    n2 = dn.size & ~1
    if n2 >= (2 << 20):  # split across the shared pool like huffman.encode
        from .huffman import _executor

        k = (n2 // 2) & ~1
        parts = list(_executor().map(_hist_pairs_np, (dn[:k], dn[k:n2])))
        hist = parts[0] + parts[1]
    else:
        hist = _hist_pairs_np(dn[:n2]) if n2 else np.zeros(256, np.int64)
    if dn.size != n2:
        hist = hist.copy()
        hist[dn[-1]] += 1
    return hist.astype(np.int64)


# ----------------------------------------------------------------------- hf
#
# The emission is the two-phase GPU Huffman recast for XLA: phase A is a
# fused gather/scan kernel producing per-pair word contributions and
# per-chunk sizes; phase B reduces contributions into the 32-bit big-endian
# word stream with gathers against an *exclusive prefix sum* — the same
# cumsum-and-boundary-gather identity as the numpy `_segment_sum`, chosen
# because XLA:CPU scatters are an order of magnitude slower than its
# gathers. The word-boundary table (`bounds[j]` = first pair whose bits
# start in word j) rides a small host assist: pair starts are at most 32
# bits apart inside a chunk, so every word contains a pair start and the
# boundary flags — 1 bit per pair — are simply `flatnonzero`'d on host
# (plus a rare one-word-skip repair at byte-aligned chunk seams, detected
# from per-chunk scalars). Only those flags (n/16 bytes) and O(nck)
# scalars cross to host mid-encode.

def _pair_tables(lens: np.ndarray, codes: np.ndarray) -> np.ndarray:
    """(65536, 2) per-symbol-PAIR merge table: [v2, l2] rows.

    Indexed by the little-endian u16 view of two adjacent stream bytes, so
    the whole reduce-merge becomes ONE row gather (gather cost on XLA:CPU
    is index-bound, so fetching both fields per index beats two gathers).
    512 KiB, built once per codebook with vectorized numpy.
    """
    i = np.arange(65536, dtype=np.uint32)
    s0, s1 = i & 255, i >> 8
    l0, l1 = lens[s0].astype(np.uint32), lens[s1].astype(np.uint32)
    tblv = (codes[s0] << l1) | codes[s1]
    # i32 lanes throughout (XLA:CPU scalarizes u8/u16 arithmetic)
    return np.stack([tblv.view(np.int32), (l0 + l1).astype(np.int32)], axis=1)


@jax.jit
def _hf_emit_a(dp: jax.Array, tblc: jax.Array):
    """Phase A over full chunks (no pad lanes): per-pair contributions.

    Returns the pair values `v2`, their in-word contributions `hi`, the
    shift state `sh` (phase B rebuilds the rare spill words from v2/sh by
    gather instead of materializing a full `lo` array), `first`
    word-boundary flags, per-chunk payload bytes, chunk byte offsets, and
    each chunk's last pair-start word (for the seam-skip repair).
    """
    m = dp.shape[0]
    nck = m // _hf.CHUNK
    half = _hf.CHUNK // 2
    dpair = jax.lax.bitcast_convert_type(dp.reshape(-1, 2), jnp.uint16)
    idx = dpair.astype(jnp.int32)
    pair = tblc[idx]  # (npairs, 2) i32 rows: [v2, l2]
    v2 = jax.lax.bitcast_convert_type(pair[:, 0], jnp.uint32)
    l2 = pair[:, 1]
    # per-chunk bit offsets from the pair-length prefix sum (sums < 2^14);
    # 16-wide two-level scan keeps the sequential pass count low
    l2c = l2.reshape(nck, half)
    c16 = jnp.cumsum(l2c.reshape(nck, half // 16, 16), axis=2)
    blk = jnp.cumsum(c16[:, :, -1], axis=1)
    boff = jnp.concatenate([jnp.zeros((nck, 1), jnp.int32), blk[:, :-1]], axis=1)
    cum2 = (c16 + boff[:, :, None]).reshape(nck, half)
    chunk_bytes = (cum2[:, -1] + 7) >> 3
    byte_off = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(chunk_bytes)])
    within = cum2 - l2c  # exclusive bit offset of each pair in its chunk
    # bitpos = within + byte_off*8; only (bitpos & 31) and (bitpos >> 5)
    # are needed, and both split into chunk-scalar + lane arithmetic
    base8 = ((byte_off[:-1] & 3) << 3)[:, None]
    sh = (((within + base8) & 31) + l2c).reshape(-1)  # <= 63
    sh32 = sh.astype(jnp.uint32)
    lo = v2 << ((jnp.uint32(0) - sh32) & _U31)
    hi = jnp.where(sh > 32, v2 >> (sh32 & _U31), lo)
    # word-boundary flags: pair i starts a new word iff pair i-1 ran to or
    # past its word's end (sh >= 32; valid because full-chunk pairs always
    # have l2 >= 2). Chunk seams reset the recurrence and are repaired
    # with an nck-sized scatter against the previous chunk's last word.
    wstart = byte_off[:-1] >> 2
    last_w = wstart + ((within[:, -1] + base8[:, 0]) >> 5)
    seam = jnp.concatenate([jnp.ones(1, bool), wstart[1:] > last_w[:-1]])
    first = jnp.concatenate([jnp.ones(1, bool), sh[:-1] >= 32])
    first = first.at[jnp.arange(nck) * half].set(seam)
    return v2, hi, sh.astype(jnp.uint16), first, chunk_bytes, byte_off, last_w


@jax.jit
def _hf_emit_b(v2, hi, sh, bounds, bad, chunk_bytes):
    """Phase B: word stream from contributions + boundary table.

    ``bounds``: (alloc+1,) i32, first-pair index per word (alloc >= words
    used; tail entries = npairs). ``bad``: words that must NOT take the
    spill of pair ``bounds[j]-1`` (the word after a seam skip), padded
    with out-of-range indices. Word j = sum of hi over its pairs (disjoint
    bits, so sum == OR) | the spill of the last pair of word j-1 — the
    spill is a sparse gather from (v2, sh), never a dense array. Returns
    (bits bytes padded to the word allocation, chunk-size u16 bytes).
    """
    c16 = jnp.cumsum(hi.reshape(-1, 16), axis=1)
    blko = jnp.concatenate([jnp.zeros(1, jnp.uint32), jnp.cumsum(c16[:, -1])[:-1]])
    csum = (c16 + blko[:, None]).reshape(-1)  # inclusive prefix sum of hi

    b = bounds
    bm1 = jnp.maximum(b - 1, 0)
    g = jnp.where(b > 0, csum[bm1], jnp.uint32(0))  # exclusive sum at b
    words = g[1:] - g[:-1]
    p = bm1[:-1]
    shp = sh[p].astype(jnp.uint32)
    lop = jnp.where(shp > 32, v2[p] << ((jnp.uint32(0) - shp) & _U31), jnp.uint32(0))
    sp = jnp.where(b[:-1] > 0, lop, jnp.uint32(0))
    sp = sp.at[bad].set(jnp.uint32(0), mode="drop")
    words = words | sp
    # big-endian byte order fused into the same pass as the reduction
    wbe = (
        ((words & 0xFF) << 24)
        | ((words & 0xFF00) << 8)
        | ((words >> 8) & 0xFF00)
        | (words >> 24)
    )
    bits = jax.lax.bitcast_convert_type(wbe, jnp.uint8).reshape(-1)
    cb = jax.lax.bitcast_convert_type(
        chunk_bytes.astype(jnp.uint16), jnp.uint8
    ).reshape(-1)
    return bits, cb


def _slab_bridge(emit_a_out, m: int):
    """Host assist + phase-B dispatch for one slab's phase-A outputs.

    Builds the word-boundary table from the flag bits and the per-chunk
    scalars (see the section comment); the ``to_host`` pulls block on
    this slab's phase A only, so other slabs' device work keeps running.
    """
    nck = m // _hf.CHUNK
    v2, hi, sh, first, chunk_bytes, byte_off, last_w = emit_a_out
    firsts = np.flatnonzero(to_host(first)).astype(np.int32)
    bo = to_host(byte_off)
    lws = to_host(last_w)
    total = int(bo[-1])
    nwords = (total + 3) >> 2
    # seam skips: chunk payloads are byte- (not word-) aligned, so the gap
    # between the last pair start of chunk c-1 and the first of chunk c can
    # reach 39 bits and hop over one word entirely
    fw = (bo[:-1] >> 2).astype(np.int64)
    skip_mask = fw[1:] >= lws[:-1].astype(np.int64) + 2
    skip_words = fw[1:][skip_mask] - 1
    ins = (skip_words - np.arange(skip_words.size)).astype(np.int64)
    bounds_core = np.insert(firsts, ins, firsts[ins]) if ins.size else firsts
    # bucketed word allocation: jit shapes recompile per bucket, not per byte
    nw = m // 2
    wbucket = max(nw // 8, 4096)
    alloc = min(-(-max(nwords, 1) // wbucket) * wbucket, nw)
    bounds = np.empty(alloc + 1, np.int32)
    bounds[: bounds_core.size] = bounds_core
    bounds[bounds_core.size :] = nw
    bad = np.full(max(nck, 1), alloc + 1, np.int32)  # out of range: dropped
    bad[: skip_words.size] = (skip_words + 1).astype(np.int32)
    bits, cb = _hf_emit_b(v2, hi, sh, to_device(bounds), to_device(bad), chunk_bytes)
    return bits[:total], cb


def _hist_pairs_np(dn: np.ndarray) -> np.ndarray:
    c = np.bincount(dn.view(np.uint16), minlength=65536).reshape(256, 256)
    return c.sum(axis=0) + c.sum(axis=1)


_PAR_SLAB = 1 << 21  # symbols per thread-parallel slab on the CPU backend


def hf_encode_device(data):
    """Device Huffman encode; payload bytes == ``huffman.encode``'s.

    Streams larger than ``_PAR_SLAB`` split into chunk-aligned slabs whose
    phase-A kernels are all dispatched before any bridge blocks — XLA
    drains the queue asynchronously, so slab i's host assist hides behind
    slab i+1's device work. Slab payloads concatenate byte-exactly (the
    same chunk-aligned-split property the threaded numpy encoder relies
    on), and each slab's bit cursors stay inside u32.
    """
    d = as_device_u8(data)
    n = int(d.size)
    hist = histogram256_device(d)
    lens = _hf.code_lengths(hist)
    codes, lens, *_ = _hf.canonical_codes(lens)
    tbl_np = (lens.astype(np.uint32) << np.uint32(16)) | codes
    n_full = (n // _hf.CHUNK) * _hf.CHUNK
    cb_parts, bit_parts = [], []
    if n_full:
        tblc = to_device(_pair_tables(lens, codes))
        slab_syms = min(_PAR_SLAB, _SLAB_CHUNKS * _hf.CHUNK)  # u32 cursors
        slab_syms = max(slab_syms - slab_syms % _hf.CHUNK, _hf.CHUNK)  # chunk-aligned
        cuts = list(range(0, n_full, slab_syms)) + [n_full]
        # dispatch every slab's phase A up front — XLA executes the queue
        # concurrently, so slab i's host bridge hides behind slab i+1's
        # device work (the async twin of the numpy encoder's thread slabs)
        outs = [(_hf_emit_a(d[a:b], tblc), b - a) for a, b in zip(cuts, cuts[1:])]
        for out, m in outs:
            bits, cb = _slab_bridge(out, m)
            cb_parts.append(cb)
            bit_parts.append(bits)
    if n > n_full or n == 0:  # partial/empty tail chunk: reference encoder
        tail_bits, tail_cb = _hf._encode_slab(to_host(d[n_full:]), tbl_np)
        cb_parts.append(to_device(np.frombuffer(tail_cb.tobytes(), np.uint8)))
        bit_parts.append(to_device(np.frombuffer(tail_bits, np.uint8)))
    payload = jnp.concatenate([to_device(lens)] + cb_parts + bit_parts)
    chunk_bytes = np.concatenate([to_host(p) for p in cb_parts]).view("<u2")
    return payload, dict({"n": n}, **_hf.offset_table(chunk_bytes))


# hf decode limits: past these the twin hands the stream to the numpy
# reference decoder (which slabs/groups internally) and re-uploads.
_HF_DEC_MAX_BYTES = _hf._DECODE_GROUP_BYTES


@functools.partial(jax.jit, static_argnums=(3,))
def _hf_dec(be: jax.Array, cursors: jax.Array, lut: jax.Array, maxlen: int):
    """All chunks decode in lockstep: one lane per chunk, CHUNK/2 steps.

    ``be``: the bitstream as big-endian u32 words (padded). ``cursors``:
    per-lane absolute *bit* cursors (u32, from the header's byte-offset
    table ×8). Each step peeks 32 bits straddling a word boundary and
    resolves TWO symbols through the (len<<8|sym) prefix LUT — the same
    double-symbol peek as the numpy ``_span_pairs`` hot loop, so lane c
    step t yields exactly symbol ``c*CHUNK + 2t``. Everything stays u32
    (x64 is off; mixed-width promotion would upcast). Out-of-range word
    gathers clamp (jnp default), which only feeds garbage to lanes that
    are past their chunk's real symbol count — trimmed by the caller.
    """
    beS1 = jnp.concatenate([be[1:], jnp.zeros(1, jnp.uint32)]) >> 1
    shift = jnp.uint32(32 - maxlen)

    def step(cur, _):
        q = cur >> 5
        r = cur & _U31
        peek = (be[q] << r) | (beS1[q] >> (_U31 - r))
        e1 = lut[peek >> shift]
        ls1 = e1 >> 8
        e2 = lut[(peek << ls1) >> shift]
        return cur + ls1 + (e2 >> 8), jnp.stack([e1, e2]).astype(jnp.uint8)

    _, sym = jax.lax.scan(step, cursors, None, length=_hf.CHUNK // 2)
    # (CHUNK/2 steps, 2 syms, lanes) -> (CHUNK, lanes)
    return sym.reshape(_hf.CHUNK, -1)


def hf_decode_device(payload, header: dict):
    """Device Huffman decode; bytes == ``huffman.decode``'s.

    Needs the per-chunk byte-offset table (``header["offs"]``) to give
    every chunk lane an independent bit cursor; legacy headers (no table,
    or hex ``"lens"`` streams), oversized payloads, and >16-bit codebooks
    decode through the host reference path and re-upload.
    """
    n = int(header["n"])
    if n == 0:
        return jnp.zeros(0, jnp.uint8)
    offs = header.get("offs")
    nchunks = -(-n // _hf.CHUNK)
    usable = (
        offs is not None
        and "lens" not in header
        and len(offs) == 4 * nchunks
    )
    if usable:
        src = payload if is_device(payload) else None
        hp = None if src is not None else _host_u8(payload)
        lens = to_host(src[:256]) if src is not None else hp[:256]
        maxlen = int(lens.max(initial=0))
        total = (int(src.size) if src is not None else hp.size) - 256 - 2 * nchunks
        usable = 0 < maxlen <= _hf.MAXLEN and 0 <= total <= _HF_DEC_MAX_BYTES
    if not usable:
        return to_device(_hf.decode(_host_u8(payload), header))
    _, lens_c, first_code, sym_table, offsets, counts = _hf.canonical_codes(
        lens.astype(np.uint8)
    )
    lut = to_device(
        _hf._pair_lut(first_code, counts, sym_table, offsets, maxlen).astype(np.uint32)
    )
    bits0 = 256 + 2 * nchunks
    bits = src[bits0:] if src is not None else to_device(hp[bits0:])
    # pow2-bucketed word allocation: +8 bytes slack like the numpy _be32,
    # padded with zeros so garbage lanes read zeros, not uninitialized mem
    balloc = max(4096, 1 << (total + 8 - 1).bit_length())
    bits = jnp.concatenate([bits, jnp.zeros(balloc - total, jnp.uint8)])
    w = bits.reshape(-1, 4).astype(jnp.uint32)
    be = (w[:, 0] << 24) | (w[:, 1] << 16) | (w[:, 2] << 8) | w[:, 3]
    byte_off = np.frombuffer(offs, "<u4")
    calloc = max(64, 1 << (nchunks - 1).bit_length())
    cur = np.zeros(calloc, np.uint32)
    cur[:nchunks] = byte_off * np.uint32(8)
    out_t = _hf_dec(be, to_device(cur), lut, maxlen)
    return out_t[:, :nchunks].T.reshape(-1)[:n]


# ------------------------------------------------------------------ rre/rze
def _level_bytes(nsym: int) -> list[int]:
    """Byte sizes of the recursive bitmap's levels for ``nsym`` flags, the
    top level last (``rre._compress_bitmap``'s recursion, sizes only)."""
    sizes = [(nsym + 7) // 8]
    while sizes[-1] > _rre._BITMAP_FLOOR:
        sizes.append((sizes[-1] + 7) // 8)
    return sizes


@functools.partial(jax.jit, static_argnums=(2,))
def _rr_flags(viewp: jax.Array, nsym: jax.Array, zero_mode: bool):
    """Flags, packed bitmap and payload counts for RRE (``zero_mode=False``)
    / RZE.

    ``viewp``: (nsym_p, k) u8 rows, nsym_p % 8 == 0, rows past ``nsym``
    zero. Returns (flags, MSB-first packed bitmap over nsym_p flags,
    counts): ``counts`` is i32 [kept rows, non-zero bytes of bitmap level
    0, 1, ...], one entry per level ``nsym_p`` flags would recurse through
    (``nsym``'s levels are a prefix of them). Byte j of level i is non-zero
    iff a flag in [j * 8^(i+1), (j+1) * 8^(i+1)) is set, and the pad flags
    are false, so every count is a fixed-shape reduction.
    """
    nsym_p = viewp.shape[0]
    v32 = viewp.astype(jnp.int32)  # i32 lanes: XLA:CPU scalarizes u8 math
    if zero_mode:
        flags = (v32 != 0).any(axis=1)
    else:
        flags = jnp.concatenate(
            [jnp.ones(1, bool), (v32[1:] != v32[:-1]).any(axis=1)]
        )
    flags = flags & (jnp.arange(nsym_p) < nsym)
    # MSB-first bit packing (np.packbits layout)
    wts = jnp.left_shift(jnp.int32(1), 7 - jax.lax.iota(jnp.int32, 8))
    bitmap = (flags.reshape(-1, 8) * wts).sum(axis=1).astype(jnp.uint8)
    counts = [flags.sum(dtype=jnp.int32)]
    nz = flags
    for _ in _level_bytes(nsym_p)[:-1]:
        nz = jnp.pad(nz, (0, -nz.size % 8)).reshape(-1, 8).any(axis=1)
        counts.append(nz.sum(dtype=jnp.int32))
    return flags, bitmap, jnp.stack(counts)


def _rr_size(counts: np.ndarray, nsym: int, k: int) -> int:
    """Exact payload bytes from ``_rr_flags``' counts: the meta, the
    non-zero bytes of every recursed level, the top level, the kept rows."""
    levels = _level_bytes(nsym)
    n_levels = len(levels) - 1
    body = sum(int(c) for c in counts[1 : 1 + n_levels])
    return 4 + 16 * n_levels + body + levels[-1] + int(counts[0]) * k


@jax.jit
def _rr_gather(viewp: jax.Array, idx: jax.Array):
    return viewp[idx]


def _rr_encode_device(data, k: int, zero_mode: bool, limit=None):
    """Shared RRE/RZE device encode; see ``rre_encode_device``."""
    d = as_device_u8(data)
    n = int(d.size)
    nsym = -(-n // k)
    header = {"n": n, "k": k, "nsym": nsym}
    if nsym == 0:
        counts = np.zeros(1, np.int32)
    else:
        nsym_p = -(-nsym // _SYM_PAD) * _SYM_PAD  # row bucket: bounds recompiles
        pad = nsym_p * k - n
        if pad:
            d = jnp.concatenate([d, jnp.zeros(pad, jnp.uint8)])
        viewp = d.reshape(nsym_p, k)
        flags, bitmap_p, counts = _rr_flags(viewp, jnp.int32(nsym), zero_mode)
        counts = to_host(counts)
    if limit is not None and _rr_size(counts, nsym, k) >= limit(header):
        return None, header  # decided from the size: no compaction
    if nsym == 0:
        z = np.zeros(0, np.uint8)
        payload, _ = _rre._serialize(z, [], [], z, n, k, 0)
        return to_device(np.frombuffer(payload, np.uint8)), header
    # kept-row compaction: the scan's output indices are the flag
    # positions; flatnonzero rides the host (XLA:CPU scatters are slow,
    # its gathers are not), the row gather stays on device
    count = int(counts[0])
    alloc = max(-(-count // _SYM_PAD) * _SYM_PAD, _SYM_PAD)
    idx = np.zeros(alloc, np.int32)
    idx[:count] = np.flatnonzero(to_host(flags))
    kept_p = _rr_gather(viewp, to_device(idx))
    # the packed bitmap (1/8k of the stream) is all the host recursion needs
    bitmap = to_host(bitmap_p)[: (nsym + 7) // 8]
    top, levels, sizes = _rre._compress_bitmap(bitmap)
    meta = (
        np.asarray([top.size, len(levels)], "<u2").tobytes()
        + np.asarray(list(sizes) + [lv.size for lv in levels], "<u8").tobytes()
    )
    head = meta + top.tobytes() + b"".join(lv.tobytes() for lv in levels)
    payload = jnp.concatenate(
        [to_device(np.frombuffer(head, np.uint8)), kept_p[:count].reshape(-1)]
    )
    return payload, header


def rre_encode_device(data, k: int, limit=None):
    """Device RRE-k; payload bytes == ``rre.rre_encode``'s.

    The payload's exact size comes from the flags first. ``limit``, if
    given, maps the header to the payload size at which the caller stores
    the stage through (``pipelines.encode``); a payload that reaches it is
    returned as ``None`` after one pull of a few scalars, and no kept-row
    compaction, bitmap pull or concatenation runs.
    """
    return _rr_encode_device(data, k, False, limit)


def rze_encode_device(data, k: int, limit=None):
    """Device RZE-k; payload bytes == ``rre.rze_encode``'s; ``limit`` as
    for :func:`rre_encode_device`."""
    return _rr_encode_device(data, k, True, limit)


@functools.partial(jax.jit, static_argnums=(2,))
def _rr_expand(bitmap: jax.Array, kept: jax.Array, zero_mode: bool):
    """Inverse of flags+compaction: expand kept rows back over all symbols.

    ``bitmap``: packed MSB-first flags (padded, pad bits zero). ``kept``:
    (alloc, k) rows, rows past the real count zero. RRE replays row
    ``cumsum(flags)-1`` everywhere (run expansion); RZE gathers the same
    but zeroes unflagged rows. A gather, not a scatter — XLA:CPU scatters
    run an order of magnitude behind its gathers (same trade as encode).
    """
    shifts = 7 - jax.lax.iota(jnp.int32, 8)
    bits = ((bitmap.astype(jnp.int32)[:, None] >> shifts) & 1).reshape(-1)
    idx = jnp.cumsum(bits) - 1
    rows = kept[jnp.maximum(idx, 0)]
    if zero_mode:
        rows = jnp.where((bits == 1)[:, None], rows, jnp.uint8(0))
    return rows


def _rr_decode_device(payload, header: dict, zero_mode: bool):
    """Shared RRE/RZE device decode; bytes == the numpy decoders'."""
    n, k, nsym = int(header["n"]), int(header["k"]), int(header["nsym"])
    if nsym == 0:
        return jnp.zeros(0, jnp.uint8)
    if "top" in header:  # legacy hex-in-JSON header: host reference path
        dec = _rre.rze_decode if zero_mode else _rre.rre_decode
        return to_device(dec(_host_u8(payload).tobytes(), header))
    src = payload if is_device(payload) else None
    hp = None if src is not None else _host_u8(payload)

    def pull(a, b):
        return to_host(src[a:b]) if src is not None else hp[a:b]

    # the recursive-bitmap metadata is tiny (1/8k of the stream): pull it
    # to host for the level recursion, keep the kept rows device-side
    top_len, n_levels = (int(v) for v in np.frombuffer(pull(0, 4), "<u2"))
    off = 4 + 8 * 2 * n_levels
    szs = np.frombuffer(pull(4, off), "<u8")
    sizes = [int(s) for s in szs[:n_levels]]
    lvl_sizes = [int(s) for s in szs[n_levels:]]
    top = pull(off, off + top_len)
    off += top_len
    levels = []
    for ls in lvl_sizes:
        levels.append(pull(off, off + ls))
        off += ls
    bitmap = _rre._decompress_bitmap(top, levels, sizes)
    count = int(np.unpackbits(bitmap, count=nsym).sum())
    kept = src[off:] if src is not None else to_device(hp[off:])
    # bucketed allocations (pad rows/bits zero) bound recompiles
    nsym_p = -(-nsym // _SYM_PAD) * _SYM_PAD
    bm = np.zeros(nsym_p // 8, np.uint8)
    bm[: bitmap.size] = bitmap
    alloc = max(-(-count // _SYM_PAD) * _SYM_PAD, _SYM_PAD)
    kept_p = jnp.concatenate(
        [kept, jnp.zeros(alloc * k - count * k, jnp.uint8)]
    ).reshape(alloc, k)
    rows = _rr_expand(to_device(bm), kept_p, zero_mode)
    return rows.reshape(-1)[:n]


def rre_decode_device(payload, header: dict):
    """Device RRE-k decode; bytes == ``rre.rre_decode``'s."""
    return _rr_decode_device(payload, header, zero_mode=False)


def rze_decode_device(payload, header: dict):
    """Device RZE-k decode; bytes == ``rre.rze_decode``'s."""
    return _rr_decode_device(payload, header, zero_mode=True)


# --------------------------------------------------------------------- tcms
@jax.jit
def _tcms_core(viewp: jax.Array) -> jax.Array:
    """Bytewise two's-complement -> sign-magnitude over little-endian rows."""
    v = viewp.astype(jnp.int32)  # i32 lanes; ~x bytewise == 255 - x
    neg = (v[:, -1] & 0x80) != 0
    out = jnp.where(neg[:, None], 255 - v, v)
    out = out.at[:, -1].set(jnp.where(neg, out[:, -1] ^ 0x80, out[:, -1]))
    return out.astype(jnp.uint8)


def tcms_encode_device(data, k: int):
    """Device TCMS-k; payload bytes == ``tcms.tcms_encode``'s."""
    d = as_device_u8(data)
    n = int(d.size)
    nsym = -(-n // k) if n else 0
    nsym_p = max(-(-nsym // _SYM_PAD) * _SYM_PAD, _SYM_PAD)
    pad = nsym_p * k - n
    if pad:
        d = jnp.concatenate([d, jnp.zeros(pad, jnp.uint8)])
    out = _tcms_core(d.reshape(nsym_p, k))[:nsym]
    return out.reshape(-1), {"n": n, "k": k}


@jax.jit
def _tcms_inv_core(viewp: jax.Array) -> jax.Array:
    """Inverse bijection: numpy's ``~(x ^ msb)`` done bytewise on rows."""
    v = viewp.astype(jnp.int32)
    neg = (v[:, -1] & 0x80) != 0  # little-endian rows: last byte is the MSB
    w = v.at[:, -1].set(v[:, -1] ^ 0x80)  # x ^ msb
    out = jnp.where(neg[:, None], 255 - w, v)  # ~y bytewise == 255 - y
    return out.astype(jnp.uint8)


def tcms_decode_device(payload, header: dict):
    """Device TCMS-k decode; bytes == ``tcms.tcms_decode``'s."""
    n, k = int(header["n"]), int(header["k"])
    if n == 0:
        return jnp.zeros(0, jnp.uint8)
    d = as_device_u8(payload)
    nsym = -(-n // k)
    nsym_p = max(-(-nsym // _SYM_PAD) * _SYM_PAD, _SYM_PAD)
    pad = nsym_p * k - int(d.size)
    if pad:
        d = jnp.concatenate([d, jnp.zeros(pad, jnp.uint8)])
    out = _tcms_inv_core(d.reshape(nsym_p, k))[:nsym]
    return out.reshape(-1)[:n]


# --------------------------------------------------------------------- bit1
@jax.jit
def _bit1_core(arr: jax.Array) -> jax.Array:
    """jnp twin of the bitshuffle plane transpose (np.packbits bit layout)."""
    nb, block = arr.shape
    shifts = (7 - jnp.arange(8, dtype=jnp.uint8))[None, :, None]
    bits = (arr[:, None, :] >> shifts) & 1  # (nb, 8, block) u8
    g = bits.reshape(nb, 8, block // 8, 8)
    w = jnp.left_shift(jnp.int32(1), 7 - jax.lax.iota(jnp.int32, 8))
    packed = jnp.einsum("npgb,b->npg", g, w, preferred_element_type=jnp.int32)
    return packed.reshape(nb, block).astype(jnp.uint8)


def bit1_encode_device(data, block: int = _BIT1_BLOCK):
    """Device BIT1; payload bytes == ``bitshuffle.bitshuffle_encode``'s.

    Compiled on TPU this is the Pallas bitshuffle kernel; elsewhere the jnp
    twin (same arithmetic, no interpret-mode overhead). Both produce the
    host encoder's 8192-byte-block plane layout.
    """
    d = as_device_u8(data)
    n = int(d.size)
    if n == 0:
        return jnp.zeros(0, jnp.uint8), {"n": 0, "block": int(block)}
    pad = (-n) % block
    if pad:
        d = jnp.concatenate([d, jnp.zeros(pad, jnp.uint8)])
    arr = d.reshape(-1, block)
    if _on_tpu():
        from repro.kernels.bitshuffle.bitshuffle import bitshuffle_pallas_raw

        planes = bitshuffle_pallas_raw(arr, False)
    else:
        planes = _bit1_core(arr)
    return planes.reshape(-1), {"n": n, "block": int(block)}


@jax.jit
def _bit1_inv_core(arr: jax.Array) -> jax.Array:
    """jnp twin of the bitshuffle inverse (plane rows -> original bytes)."""
    nb, block = arr.shape
    shifts = (7 - jnp.arange(8, dtype=jnp.uint8))[None, None, :]
    # payload byte (plane p, group q) holds bit p of bytes 8q..8q+7
    bits = ((arr.reshape(nb, 8, block // 8)[:, :, :, None] >> shifts) & 1).reshape(
        nb, 8, block
    )
    w = jnp.left_shift(jnp.int32(1), 7 - jax.lax.iota(jnp.int32, 8))
    out = jnp.einsum("npq,p->nq", bits, w, preferred_element_type=jnp.int32)
    return out.astype(jnp.uint8)


def bit1_decode_device(payload, header: dict):
    """Device BIT1 decode; bytes == ``bitshuffle.bitshuffle_decode``'s.

    Pallas inverse kernel on TPU, the jnp twin elsewhere — same bit layout
    either way.
    """
    n, block = int(header["n"]), int(header["block"])
    if n == 0:
        return jnp.zeros(0, jnp.uint8)
    arr = as_device_u8(payload).reshape(-1, block)
    if _on_tpu():
        from repro.kernels.bitshuffle.bitshuffle import bitunshuffle_pallas_raw

        out = bitunshuffle_pallas_raw(arr, False)
    else:
        out = _bit1_inv_core(arr)
    return out.reshape(-1)[:n]
