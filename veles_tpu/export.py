"""Trained-workflow export: a Python-independent inference artifact.

Capability parity with the reference export + libVeles loader
(reference: libVeles/src/workflow_loader.cc:46-131 — extract archive,
parse unit table, build an executable chain; libVeles/inc/veles/unit.h:41
— ``Unit::Execute`` forward over float buffers): a trained
:class:`~veles_tpu.accelerated_units.AcceleratedWorkflow`'s forward
chain is serialized to a versioned tar.gz holding

* ``manifest.json`` — format version, unit table (type MAPPING +
  numeric config), input/output specs, provenance;
* ``weights.npz`` — all parameters as named float32 arrays;
* ``model.bin`` — the same topology+weights in a flat binary layout
  the native C++ runtime (``native/veles_infer.cc``) parses without
  Python, JSON, or zlib.

:class:`ExportedModel` re-executes the chain from the artifact alone —
``forward()`` builds a jitted jax chain (serving path, TPU-capable),
``forward_numpy()`` is a dependency-free reference used to validate
the native runtime.
"""

import collections
import hashlib
import io
import json
import struct
import tarfile
import threading
import time

import numpy

from .distributable import SniffedLock
from .error import Bug
from .json_encoders import dumps_json

FORMAT_NAME = "veles-tpu-model"
FORMAT_VERSION = 1
MAGIC = b"VTPM"

#: Unit types the artifact format understands, with their exportable
#: numeric config keys.
EXPORTABLE = {
    "all2all": (), "all2all_tanh": (), "all2all_relu": (),
    "all2all_str": (), "all2all_sigmoid": (), "softmax": (),
    "conv": ("kx", "ky", "n_kernels"),
    "conv_tanh": ("kx", "ky", "n_kernels"),
    "conv_relu": ("kx", "ky", "n_kernels"),
    "conv_str": ("kx", "ky", "n_kernels"),
    "conv_sigmoid": ("kx", "ky", "n_kernels"),
    "max_pooling": ("kx", "ky"),
    "maxabs_pooling": ("kx", "ky"),
    "avg_pooling": ("kx", "ky"),
    "norm": ("alpha", "beta", "k", "n"),
    "dropout": (),
    "mean_disp": (),
    "activation_tanh": (), "activation_relu": (),
    "activation_str": (), "activation_sigmoid": (),
    # Long tail (reference unit_factory.cc registers every forward
    # type): RBM inference = sigmoid dense over the CD-trained
    # weights; tied-weight deconv decoders; Kohonen BMU distances.
    "rbm": (),
    "all2all_deconv": (), "all2all_deconv_sigmoid": (),
    "all2all_deconv_tanh": (),
    "kohonen": (),
    # Transformer family (no reference counterpart — the TPU build's
    # long-context extension, deployable like everything else).
    "embedding": ("vocab_size", "embed_dim"),
    "transformer_block": ("n_heads",),
    "lm_head": (),
}

#: Unit kinds that train but that no serving program knows yet: the
#: spec-built LM layer (rotary positions, grouped keys/values, the
#: short convolution's state, a held share of experts, a window, an
#: output gate, sandwich norms, a shared expert) and the RMS norm
#: before its head.  Refused by name, not as "unknown"; so is an
#: embedding whose output is scaled.
TRAIN_ONLY = ("lm_layer", "rms_norm")

TANH_A, TANH_B = 1.7159, 0.6666


def _unit_entry(unit):
    """manifest entry + {param_name: array} for one forward unit."""
    mapping = getattr(type(unit), "MAPPING", None)
    from .mean_disp_normalizer import MeanDispNormalizer
    if isinstance(unit, MeanDispNormalizer):
        mapping = "mean_disp"
    if mapping in TRAIN_ONLY:
        raise Bug("unit %s: %s units train but are not served yet — "
                  "export has no forward, cached or paged program "
                  "for a layer built from a spec (docs/attention.md, "
                  "\"Layers from a spec\")" % (unit.name, mapping))
    if mapping == "embedding" and getattr(unit, "scale", 1.0) != 1.0:
        raise Bug("unit %s: an embedding scaled by %r trains but is "
                  "not served yet (samples/trinity.py)" %
                  (unit.name, unit.scale))
    if mapping not in EXPORTABLE:
        raise Bug("unit %s (type %s, MAPPING %r) is not exportable" %
                  (unit.name, type(unit).__name__, mapping))
    config = {}
    for key in EXPORTABLE[mapping]:
        config[key] = getattr(unit, key)
    # Geometry carried uniformly when present.
    for key in ("padding", "sliding"):
        if hasattr(unit, key):
            config[key] = getattr(unit, key)
    if hasattr(unit, "output_sample_shape") and \
            unit.output_sample_shape is not None:
        config["output_sample_shape"] = list(unit.output_sample_shape)
    params = {}
    if mapping == "mean_disp":
        for pname in ("mean", "rdisp"):
            vec = getattr(unit, pname)
            vec.map_read()
            params[pname] = numpy.asarray(
                vec.mem, dtype=numpy.float32)
    elif mapping == "rbm":
        # Inference forward is h = sigmoid(v·W + c): the visible bias
        # only matters for the training-time Gibbs chain, so the
        # artifact carries weights + hidden bias and rides the dense
        # execution path (reference libVeles executes every unit as a
        # forward-only chain, unit.h:41).
        unit.weights.map_read()
        params["weights"] = numpy.asarray(unit.weights.mem,
                                          dtype=numpy.float32)
        if unit.include_bias and unit.bias:
            unit.bias.map_read()
            params["bias"] = numpy.asarray(unit.bias.mem,
                                           dtype=numpy.float32)
    elif mapping.startswith("all2all_deconv"):
        # Tied weights live on the paired encoder; the standalone
        # artifact materializes them transposed so the decoder is an
        # ordinary dense unit (y = x·Wᵀ + b  →  x·(Wᵀ) with W stored
        # pre-transposed) for every runtime.
        enc_w = unit.encoder.weights
        enc_w.map_read()
        w = numpy.asarray(enc_w.mem, dtype=numpy.float32)
        params["weights"] = numpy.ascontiguousarray(w.T)
        if unit.include_bias and unit.vbias:
            unit.vbias.map_read()
            params["bias"] = numpy.asarray(unit.vbias.mem,
                                           dtype=numpy.float32)
        config["output_sample_shape"] = [int(w.shape[0])]
    elif mapping == "kohonen":
        unit.weights.map_read()
        params["weights"] = numpy.asarray(unit.weights.mem,
                                          dtype=numpy.float32)
        config["output_sample_shape"] = [int(unit.n_neurons)]
    elif mapping == "embedding":
        for pname, vec in unit.trainables.items():
            vec.map_read()
            params[pname] = numpy.asarray(vec.mem,
                                          dtype=numpy.float32)
    elif mapping == "transformer_block":
        config["causal"] = int(unit.causal)
        for pname, vec in unit.trainables.items():
            vec.map_read()
            params[pname] = numpy.asarray(vec.mem,
                                          dtype=numpy.float32)
    elif mapping == "lm_head":
        # Tied heads materialize the embedding weights transposed so
        # the artifact is standalone (same treatment as deconv).
        if unit.tie_to is not None:
            src = unit.tie_to.weights
            src.map_read()
            w = numpy.ascontiguousarray(
                numpy.asarray(src.mem, dtype=numpy.float32).T)
        else:
            unit.weights.map_read()
            w = numpy.asarray(unit.weights.mem, dtype=numpy.float32)
        params["weights"] = w
        if unit.include_bias and unit.bias:
            unit.bias.map_read()
            params["bias"] = numpy.asarray(unit.bias.mem,
                                           dtype=numpy.float32)
        config["output_sample_shape"] = [int(w.shape[1])]
    else:
        for pname, vec in getattr(unit, "trainables", {}).items():
            if not vec:
                continue
            vec.map_read()
            params[pname] = numpy.asarray(
                vec.mem, dtype=numpy.float32)
    return {"name": unit.name, "type": mapping,
            "config": config}, params


def forward_chain(workflow):
    """The exportable forward units, in execution order.  Uses the
    ``forwards`` convention (every sample workflow defines it), with
    any normalizer between loader and first layer included."""
    chain = []
    forwards = getattr(workflow, "forwards", None)
    if not forwards:
        raise Bug("workflow %s has no .forwards chain to export"
                  % workflow.name)
    first = forwards[0]
    norm = getattr(workflow, "normalizer", None)
    if norm is not None and getattr(first, "input", None) is \
            getattr(norm, "output", None):
        chain.append(norm)
    chain.extend(forwards)
    return chain


def _expand_unit(unit):
    """One forward unit → one or more (entry, params) pairs.  A
    pipelined transformer stack (stage-stacked parameters with a
    leading n_blocks dim) UNSTACKS into n_blocks ordinary
    transformer_block entries — the pipeline is a TRAINING layout,
    not an inference format, so a stack trained under dp×pp deploys
    through the same artifact/native/REST surfaces as a sequential
    model (sequential and pipelined are bit-identical by
    construction, ops/pipeline.py)."""
    from .znicz.attention import PipelinedTransformerStack
    if not isinstance(unit, PipelinedTransformerStack):
        return [_unit_entry(unit)]
    out = []
    for i in range(unit.n_blocks):
        params = {}
        for pname, vec in unit.trainables.items():
            vec.map_read()
            params[pname] = numpy.ascontiguousarray(
                numpy.asarray(vec.mem, dtype=numpy.float32)[i])
        entry = {"name": "%s_block%d" % (unit.name, i),
                 "type": "transformer_block",
                 "config": {"n_heads": unit.n_heads,
                            "causal": int(unit.causal)}}
        out.append((entry, params))
    return out


def export_workflow(workflow, path):
    """Writes the inference artifact for a trained workflow."""
    chain = forward_chain(workflow)
    units = []
    weight_arrays = {}
    for unit in chain:
        for entry, params in _expand_unit(unit):
            entry["params"] = {}
            for pname, arr in params.items():
                key = "%s__%s" % (entry["name"], pname)
                if key in weight_arrays:
                    raise Bug("duplicate weight key %r — unit names "
                              "in the chain must be unique" % key)
                weight_arrays[key] = arr
                entry["params"][pname] = key
            units.append(entry)
    for entry in units:
        shape = entry["config"].get("output_sample_shape")
        if shape is not None and len(shape) > 1:
            # model.bin flattens dense outputs to n_out; a spatial
            # dense output feeding a conv/pool would lose geometry in
            # the native runtime — refuse rather than mis-execute.
            raise Bug("unit %s has multi-dim dense output shape %s — "
                      "not representable in the native artifact" %
                      (entry["name"], shape))
    in_vec = chain[0].input
    out_vec = chain[-1].output
    manifest = {
        "format": FORMAT_NAME,
        "version": FORMAT_VERSION,
        "workflow": type(workflow).__name__,
        "checksum": workflow.checksum,
        "created": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "input": {"sample_shape": list(in_vec.shape[1:]),
                  # Token models declare int32; the wire format for
                  # forward() inputs stays float (values are cast).
                  "dtype": str(in_vec.dtype)},
        "output": {"sample_shape": list(out_vec.shape[1:])},
        "units": units,
    }
    npz_buf = io.BytesIO()
    numpy.savez(npz_buf, **weight_arrays)
    blobs = {
        "manifest.json": dumps_json(manifest, indent=2).encode(),
        "weights.npz": npz_buf.getvalue(),
        "model.bin": _pack_binary(manifest, weight_arrays),
    }
    # Level 1: f32 weights are nearly incompressible (7% at any
    # level) and level 9 only makes the write slower — 281 s against
    # ~160 s for the 5 GB artifact of the 640M LM.
    with tarfile.open(path, "w:gz", compresslevel=1) as tar:
        for name, blob in blobs.items():
            info = tarfile.TarInfo(name)
            info.size = len(blob)
            tar.addfile(info, io.BytesIO(blob))
    return path


# -- model.bin (native runtime format) ----------------------------------

def _pack_str(s):
    data = s.encode("utf-8")
    return struct.pack("<H", len(data)) + data


def _flat_config(config):
    """Flattens geometry tuples into scalar keys the native parser
    reads: padding → pt/pb/pl/pr, sliding → sh/sw."""
    flat = {}
    for key, value in config.items():
        if key == "padding":
            (pt, pb), (pl, pr) = value
            flat.update(pad_top=pt, pad_bottom=pb, pad_left=pl,
                        pad_right=pr)
        elif key == "sliding":
            sh, sw = value
            flat.update(stride_h=sh, stride_w=sw)
        elif key == "output_sample_shape":
            flat["n_out"] = int(numpy.prod(value))
        else:
            flat[key] = float(value)
    return flat


def _pack_binary(manifest, weight_arrays):
    out = [MAGIC, struct.pack("<II", FORMAT_VERSION,
                              len(manifest["units"]))]
    in_shape = manifest["input"]["sample_shape"]
    out.append(struct.pack("<I", len(in_shape)))
    out.append(struct.pack("<%dI" % len(in_shape), *in_shape))
    for entry in manifest["units"]:
        out.append(_pack_str(entry["type"]))
        out.append(_pack_str(entry["name"]))
        flat = _flat_config(entry["config"])
        out.append(struct.pack("<I", len(flat)))
        for key in sorted(flat):
            out.append(_pack_str(key))
            out.append(struct.pack("<d", float(flat[key])))
        params = entry["params"]
        out.append(struct.pack("<I", len(params)))
        for pname in sorted(params):
            arr = weight_arrays[params[pname]]
            out.append(_pack_str(pname))
            out.append(struct.pack("<I", arr.ndim))
            out.append(struct.pack("<%dI" % arr.ndim, *arr.shape))
            out.append(numpy.ascontiguousarray(
                arr, dtype=numpy.float32).tobytes())
    return b"".join(out)


# -- paged KV cache: the block pool --------------------------------------

#: Storage dtypes the paged KV pool supports.  "f32" is the exact
#: path (byte-for-byte today's arithmetic — the bit-identical greedy
#: anchor); "bf16" is a scale-free cast; "int8" and "fp8" carry
#: per-(block, head) f32 scales alongside the block tensors and
#: quantize on scatter / dequantize on gather (KIVI-style block
#: granularity, so refcounts, COW, and prefix-cache sha1 keys never
#: see the quantization — they only ever address whole blocks).
KV_DTYPES = ("f32", "bf16", "int8", "fp8")

#: Symmetric clip range per scaled storage dtype (None: scale-free).
_KV_QMAX = {"f32": None, "bf16": None, "int8": 127.0, "fp8": 448.0}

#: Bytes per stored k/v element.
_KV_ITEMSIZE = {"f32": 4, "bf16": 2, "int8": 1, "fp8": 1}


def kv_dtype_supported(kv_dtype):
    """Whether this jax build can hold the storage dtype.  fp8 needs
    ``jnp.float8_e4m3fn`` (capable platforms only); the int8 and bf16
    planes work everywhere."""
    if kv_dtype not in KV_DTYPES:
        return False
    if kv_dtype != "fp8":
        return True
    import jax.numpy as jnp
    return hasattr(jnp, "float8_e4m3fn")


def check_kv_dtype(kv_dtype):
    """Canonical KV storage dtype name (None → "f32"), or Bug naming
    the valid set for unknown/unsupported names."""
    kv_dtype = "f32" if kv_dtype is None else str(kv_dtype)
    if kv_dtype not in KV_DTYPES:
        raise Bug("unknown KV storage dtype %r — valid: %s" %
                  (kv_dtype, ", ".join(KV_DTYPES)))
    if not kv_dtype_supported(kv_dtype):
        raise Bug("KV storage dtype %r is not supported by this jax "
                  "build (fp8 needs float8_e4m3fn)" % (kv_dtype,))
    return kv_dtype


def _kv_storage_jnp(kv_dtype):
    import jax.numpy as jnp
    return {"f32": jnp.float32, "bf16": jnp.bfloat16,
            "int8": jnp.int8,
            "fp8": getattr(jnp, "float8_e4m3fn", None)}[kv_dtype]


def _kv_unpack(storage):
    """``(ks, vs, sks, svs)`` from a pool storage tuple — the scale
    lists are None for scale-free (f32/bf16) pools."""
    if len(storage) == 4:
        return storage
    ks, vs = storage
    return ks, vs, None, None


def _kv_quantize(vals, scale_full, kv_dtype):
    """Quantize f32 ``vals`` at an already-broadcast ``scale_full``
    (zero scale → zero code, so never-written rows stay exact
    zeros).  int8 rounds-to-nearest; fp8 clips then lets the cast
    round — both deterministic, the paged parity gates replay
    byte-identical sessions."""
    import jax.numpy as jnp
    qmax = _KV_QMAX[kv_dtype]
    safe = jnp.where(scale_full > 0.0, scale_full, 1.0)
    x = jnp.clip(vals / safe, -qmax, qmax)
    if kv_dtype == "int8":
        x = jnp.round(x)
    return jnp.where(scale_full > 0.0, x,
                     0.0).astype(_kv_storage_jnp(kv_dtype))


class KVBlockPool(object):
    """A vLLM-style block pool for the paged serving decode path:
    the device holds one fixed tensor of ``(n_blocks, block_size, H,
    D)`` k/v blocks per layer (``storage``, owned by the model that
    built the pool), and every request addresses it through a
    per-request BLOCK TABLE of physical block ids — so N concurrent
    streams of wildly different lengths share one allocation instead
    of each owning a dense ``(B, L, H, D)`` cache sized to its max.

    This object is the HOST-side half: block accounting (free list +
    per-block refcounts), the prompt-prefix cache (full-block
    prefixes keyed by token hash, LRU-bounded, each entry holding a
    ref on its blocks so a common system prompt stays resident and
    is prefilled ONCE), and copy-on-write (a row about to WRITE into
    a shared block gets a private copy first).  Device tensors are
    opaque here — the owning model supplies ``copy_fn(storage, src,
    dst) -> storage`` and mutates ``storage`` through its own jitted
    gather/scatter programs.

    Block 0 is the TRASH block: table padding and out-of-range
    writes land there, so padded rows in a coalesced device batch
    can scatter junk without owning real blocks.  Accounting is
    lock-guarded: the engine's device thread allocates/frees while
    HTTP threads read ``occupancy()`` for ``/stats``.
    """

    TRASH = 0

    def __init__(self, n_blocks, block_size, storage=None,
                 copy_fn=None, prefix_capacity=256, kv_dtype=None,
                 block_bytes=0):
        n_blocks = int(n_blocks)
        block_size = int(block_size)
        if n_blocks < 2:
            raise Bug("a KV block pool needs >= 2 blocks (block 0 "
                      "is the trash block), got %d" % n_blocks)
        if block_size < 1:
            raise Bug("block_size must be >= 1, got %d" % block_size)
        self.n_blocks = n_blocks
        self.block_size = block_size
        self.storage = storage
        self._copy_fn = copy_fn
        # Storage dtype + per-block device bytes (geometry × itemsize
        # + scale rows): immutable after construction, so occupancy()
        # reads them lock-free — only the COUNTS need the lock.
        self.kv_dtype = check_kv_dtype(kv_dtype)
        self.block_bytes = int(block_bytes)
        self.prefix_capacity = int(prefix_capacity)
        self._lock = SniffedLock(name="KVBlockPool.lock")
        # LIFO free list: recently-freed blocks are re-used first
        # (their pages are warm).  Block 0 (trash) is never free.
        self._free = list(range(n_blocks - 1, 0, -1))  # guarded-by: _lock
        self._refs = {}  # guarded-by: _lock
        # digest -> tuple(block ids); OrderedDict as LRU (most
        # recently hit last).  Entries hold one ref per block.
        self._prefix = collections.OrderedDict()  # guarded-by: _lock
        self.prefix_hits = 0  # guarded-by: _lock
        self.prefix_misses = 0  # guarded-by: _lock
        self.cow_copies = 0  # guarded-by: _lock

    @property
    def usable(self):
        """Blocks available to requests (total minus trash)."""
        return self.n_blocks - 1

    def free_count(self):
        with self._lock:
            return len(self._free)

    def used_count(self):
        with self._lock:
            return self.usable - len(self._free)

    def blocks_for(self, n_tokens):
        """Blocks needed to hold ``n_tokens`` cache slots."""
        return -(-int(n_tokens) // self.block_size)

    # -- allocation ------------------------------------------------------

    def alloc(self, n):
        """``n`` fresh block ids (ref 1 each), or None when the pool
        cannot supply them even after evicting cached prefixes —
        the caller sheds load.  Prefix entries are evicted LRU-first
        under pressure: cached prompts are an optimization, never a
        reason to refuse live traffic."""
        n = int(n)
        with self._lock:
            while len(self._free) < n and self._prefix:
                _, ids = self._prefix.popitem(last=False)
                self._release_locked(ids)
            if len(self._free) < n:
                return None
            out = [self._free.pop() for _ in range(n)]
            for b in out:
                self._refs[b] = 1
            return out

    def retain(self, ids):
        """Adds one ref per block — the generic counterpart of
        :meth:`release` for callers that hand a table to a second
        owner (``lookup_prefix``/``register_prefix`` take their own
        refs internally)."""
        with self._lock:
            for b in ids:
                if b == self.TRASH:
                    continue
                self._refs[b] += 1

    def release(self, ids):
        """Drops one ref per block; blocks at zero return to the
        free list.  Trash ids are ignored (table padding)."""
        with self._lock:
            self._release_locked(ids)

    def refs_of(self, block_id):
        """Current refcount of one block (0 for free/trash) — the
        speculative-decode rewind path asks before writing into a
        table tail block whether anyone else holds it (refs > 1 ⇒
        copy-on-write first, exactly the prefix-sharing discipline)."""
        with self._lock:
            return self._refs.get(int(block_id), 0)

    def _release_locked(self, ids):
        for b in ids:
            if b == self.TRASH:
                continue
            left = self._refs[b] - 1
            if left:
                self._refs[b] = left
            else:
                del self._refs[b]
                self._free.append(b)

    # -- prefix sharing --------------------------------------------------

    def prefix_chain(self, tokens):
        """Chained per-block digests (digest_j = sha1(digest_{j-1} ·
        block_j tokens), the vLLM scheme): O(L) total hashing for
        every full-block prefix of a prompt, computed OUTSIDE the
        pool lock so adoption never blocks ``occupancy()`` readers
        on hashing.  Callers doing lookup-then-register pass the
        same chain to both so each prompt is hashed ONCE."""
        tokens = numpy.ascontiguousarray(tokens, dtype=numpy.int32)
        bs = self.block_size
        chain = []
        digest = b""
        for j in range(len(tokens) // bs):
            digest = hashlib.sha1(
                digest + tokens[j * bs:(j + 1) * bs].tobytes()
            ).digest()
            chain.append(digest)
        return chain

    def lookup_prefix(self, tokens, chain=None):
        """The longest cached full-block prefix of ``tokens``:
        ``(n_full_blocks_matched, block_ids)`` with one ref per
        returned block ALREADY TAKEN for the caller, or ``(0, [])``.
        Matching is by token-content hash at full-block granularity
        — a request sharing a system prompt adopts its blocks
        instead of re-prefilling them."""
        if chain is None:
            chain = self.prefix_chain(tokens)
        with self._lock:
            for j in range(len(chain), 0, -1):
                ids = self._prefix.get(chain[j - 1])
                if ids is None:
                    continue
                self._prefix.move_to_end(chain[j - 1])
                for b in ids:
                    self._refs[b] += 1
                self.prefix_hits += 1
                return j, list(ids)
            self.prefix_misses += 1
            return 0, []

    def register_prefix(self, tokens, block_ids, chain=None):
        """Registers every full-block prefix of a just-prefilled
        prompt (``block_ids`` = its table, position-ordered) so later
        requests can adopt the blocks.  Existing entries are kept
        (their blocks already hold the same content); the LRU bound
        evicts the coldest entries past ``prefix_capacity``."""
        if chain is None:
            chain = self.prefix_chain(tokens)
        with self._lock:
            for j, key in enumerate(chain, start=1):
                if key in self._prefix:
                    self._prefix.move_to_end(key)
                    continue
                ids = tuple(block_ids[:j])
                for b in ids:
                    self._refs[b] += 1
                self._prefix[key] = ids
            while len(self._prefix) > self.prefix_capacity:
                _, ids = self._prefix.popitem(last=False)
                self._release_locked(ids)

    # -- cross-pool export / adoption ------------------------------------

    def export_prefix_blocks(self, tokens, chain=None):
        """The longest cached full-block prefix of ``tokens`` as an
        EXPORTABLE handle: ``(n_full_blocks, block_ids)`` with one
        ref per block taken for the caller — identical contract to
        :meth:`lookup_prefix`, named for the disaggregation wire
        (docs/serving.md "Serving fabric"): the caller serializes the
        addressed device blocks (``ExportedModel.export_kv_blocks``)
        and then MUST :meth:`release` the ids.  The refs pin the
        blocks against eviction/COW while their bytes are in flight."""
        return self.lookup_prefix(tokens, chain=chain)

    def adopt_prefix_blocks(self, tokens, n_blocks, write_fn=None,
                            chain=None):
        """Adopts ``n_blocks`` full blocks of remotely-prefilled KV
        into THIS pool's prefix cache: allocates destination blocks,
        lets ``write_fn(ids)`` scatter the shipped tensor data into
        them (``ExportedModel.import_kv_blocks``), then registers
        every full-block prefix so the next local request with the
        same prompt adopts the blocks instead of re-prefilling.

        Refcount-correct by construction: after registration the
        alloc refs are RELEASED, so the prefix-cache entries are the
        only owners — block ``j`` (0-based) is held by entries
        ``j+1 .. n`` exactly as a locally-prefilled prefix would be,
        and LRU eviction / ``drop_prefixes`` return the blocks to the
        free list with no residue.  Idempotent: if the full chain is
        already cached the existing ids are returned untouched.
        Returns the block ids, or None when the pool cannot supply
        ``n_blocks`` even after evicting colder prefixes (the caller
        skips adoption — it is an optimization, never load-bearing)."""
        if chain is None:
            chain = self.prefix_chain(tokens)
        n_blocks = min(int(n_blocks), len(chain))
        if n_blocks <= 0:
            return []
        with self._lock:
            ids = self._prefix.get(chain[n_blocks - 1])
            if ids is not None:
                self._prefix.move_to_end(chain[n_blocks - 1])
                return list(ids)
        ids = self.alloc(n_blocks)
        if ids is None:
            return None
        if write_fn is not None:
            try:
                write_fn(ids)
            except Exception:
                self.release(ids)
                raise
        bs = self.block_size
        tokens = numpy.ascontiguousarray(tokens,
                                         dtype=numpy.int32)
        self.register_prefix(tokens[:n_blocks * bs], ids,
                             chain=chain[:n_blocks])
        self.release(ids)
        return ids

    # -- copy-on-write ---------------------------------------------------

    def cow_copy(self, block_id):
        """Copy-on-write: a fresh private block holding a device copy
        of ``block_id``'s content (the caller is about to WRITE into
        a position that falls inside a shared block — e.g. a fully
        prefix-cached prompt re-feeding its last token).  The caller
        keeps responsibility for releasing its ref on the shared
        original.  Returns the new id, or None when the pool is
        exhausted."""
        ids = self.alloc(1)
        if ids is None:
            return None
        if self._copy_fn is not None:
            self.storage = self._copy_fn(self.storage, int(block_id),
                                         int(ids[0]))
        with self._lock:
            self.cow_copies += 1
        return ids[0]

    def drop_prefixes(self):
        """Releases every cached prompt-prefix entry (blocks return
        to the free list once unreferenced) and returns how many were
        dropped.  Hot weight reload calls this: cached prefixes hold
        k/v computed under the OLD weights, and serving them to a
        post-swap request would mix two models in one sequence.  Live
        rows keep their tables — only the cache is invalidated."""
        with self._lock:
            dropped = len(self._prefix)
            while self._prefix:
                _, ids = self._prefix.popitem(last=False)
                self._release_locked(ids)
            return dropped

    # -- observability ---------------------------------------------------

    def occupancy(self):
        """The ``/stats`` pool section: block occupancy plus prefix-
        cache and COW counters, and the BYTES the blocks occupy
        (blocks × block geometry × storage dtype, scale rows
        included) — the figure that makes a quantized pool's capacity
        win visible on the dashboard."""
        with self._lock:
            used = self.usable - len(self._free)
            return {
                "block_size": self.block_size,
                "blocks_total": self.usable,
                "blocks_free": len(self._free),
                "blocks_used": used,
                "storage_dtype": self.kv_dtype,
                "block_bytes": self.block_bytes,
                "bytes_total": self.usable * self.block_bytes,
                "bytes_used": used * self.block_bytes,
                "prefix_entries": len(self._prefix),
                "prefix_hits": self.prefix_hits,
                "prefix_misses": self.prefix_misses,
                "cow_copies": self.cow_copies,
            }


# -- shared LM decode helpers -------------------------------------------
# ONE implementation of the head projection and the per-row
# greedy/temperature select, shared by the dense bucketed programs and
# the paged extend/step programs: a sampling fix applied to one copy
# but not another would silently break the documented bit-identical
# greedy guarantee between the two paths.

def _head_logits(x_last, head_w, head_b, head_s=None):
    if head_s is not None:
        # Weight-only int8 head: dequant-in-kernel — the int8 weight
        # feeds the dot directly and the per-output-channel scale
        # applies to the f32 accumulator (LLM.int8-style).
        y = (x_last @ head_w.astype(head_s.dtype)) * head_s
    else:
        y = x_last @ head_w
    return y + head_b if head_b is not None else y


def _mm(h, p, name):
    """``h @ W`` for a decode-program weight: when the parameter
    pytree carries a ``<name>__s`` per-output-channel scale (the
    weight-only int8 plane), the int8 weight feeds the dot and the
    scale applies to the accumulator — dequant-in-kernel, never a
    materialized f32 copy of the weight."""
    s = p.get(name + "__s")
    if s is None:
        return h @ p[name]
    return (h @ p[name].astype(s.dtype)) * s


def _sample_rows(logits, keys, temps):
    """Greedy/temperature select per row; temperatures are TRACED
    (never a compile key) and each row draws from its own PRNG
    stream."""
    import jax
    import jax.numpy as jnp
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    sampled = jax.vmap(jax.random.categorical)(
        keys, scaled).astype(jnp.int32)
    return jnp.where(temps > 0.0, sampled, greedy)


# -- execution from the artifact ----------------------------------------

class ExportedModel(object):
    """Loads an artifact and re-executes its forward chain
    (the Python mirror of the native runtime)."""

    def __init__(self, path, compile_capacity=32):
        if hasattr(path, "read"):
            # A file object (e.g. an already-verified in-memory blob
            # from the reload path — what was hashed is exactly what
            # loads, no second read of a file that may have changed).
            tar = tarfile.open(fileobj=path, mode="r:gz")
            self.path = getattr(path, "name", None)
        else:
            tar = tarfile.open(path, "r:gz")
            self.path = path
        with tar:
            manifest_blob = tar.extractfile("manifest.json").read()
            weights_blob = tar.extractfile("weights.npz").read()
        self.manifest = json.loads(manifest_blob)
        if self.manifest.get("format") != FORMAT_NAME:
            raise Bug("%s is not a %s artifact" % (path, FORMAT_NAME))
        if self.manifest.get("version", 0) > FORMAT_VERSION:
            raise Bug("artifact version %s is newer than this "
                      "runtime (%d)" % (self.manifest.get("version"),
                                        FORMAT_VERSION))
        self.weights = dict(numpy.load(io.BytesIO(weights_blob)))
        self.units = self.manifest["units"]
        for entry in self.units:
            # An artifact is input from outside the program: a kind
            # an older tree wrote is refused by name, not as unknown.
            if entry["type"] == "moe_transformer_block":
                raise Bug("%s: unit %s is a moe_transformer_block — "
                          "the capacity-routed expert block was "
                          "removed; expert layers are dropless "
                          "LMLayer specs (docs/moe.md), which train "
                          "but are not served yet"
                          % (path, entry["name"]))
        self.input_shape = tuple(
            self.manifest["input"]["sample_shape"])
        self._jit_forward = None
        self.compile_capacity = int(compile_capacity)
        self._compile_cache = None
        #: Monotonically increasing weight generation: 1 at load,
        #: bumped by every :meth:`swap_weights` — the serving layer
        #: surfaces it as the ``weight_version`` gauge.
        self.weight_version = 1
        self._jax_weights = None
        self._lm_params_cache = None

    @property
    def compile_cache(self):
        """The bounded LRU of built executables (generate geometries
        and forward shape sentinels) — every compile key is
        client-reachable through the serving endpoints, so the set is
        hard-capped; evicting a forward sentinel resets the monolithic
        forward jit (its per-shape cache hides behind one callable)."""
        if self._compile_cache is None:
            from .serving.buckets import CompileCache

            def on_evict(key, value):
                if key and key[0] == "fwd":
                    # The forward executables all hide behind ONE jit
                    # callable, so dropping it invalidates every fwd
                    # sentinel — remove them together or the
                    # survivors would report cache HITs while
                    # forward() silently recompiles.
                    self._jit_forward = None
                    self._compile_cache.drop_where(
                        lambda k: k and k[0] == "fwd")

            self._compile_cache = CompileCache(
                capacity=self.compile_capacity, on_evict=on_evict)
        return self._compile_cache

    @property
    def max_position(self):
        """The LM positional-table size (prompt+generated tokens must
        fit), or None when the artifact is not a causal LM."""
        try:
            emb, _, _ = self._lm_chain()
        except Bug:
            return None
        return int(self.weights[emb["params"]["pos"]].shape[0])

    # ---- hot weight swap ----------------------------------------------

    def _device_weights(self):
        """The full weight dict as device-resident arrays — one
        host→device transfer per weight generation, not per call.
        Every jitted program takes its weights from here as a TRACED
        pytree argument, so a same-geometry swap reuses the compiled
        executables (same shapes/dtypes → same program)."""
        if self._jax_weights is None:
            import jax.numpy as jnp
            self._jax_weights = {k: jnp.asarray(v)
                                 for k, v in self.weights.items()}
        return self._jax_weights

    @staticmethod
    def _decode_weight_mode():
        """The weight plane of the decode program family:
        ``root.common.serving.weight_dtype`` — "f32" (default, the
        parity anchor) or "int8" (weight-only int8 matmuls with
        per-output-channel scales, dequant-in-kernel).  The dense
        ``forward`` path never quantizes — it stays the f32 oracle
        the perplexity-delta gate compares against.  The mode string
        rides every decode compile-cache key like ``attend=`` does."""
        from .config import root, get as config_get
        mode = str(config_get(root.common.serving.weight_dtype,
                              "f32"))
        if mode not in ("f32", "int8"):
            raise Bug("unknown decode weight dtype %r — valid: "
                      "f32, int8" % (mode,))
        return mode

    #: 2-D decode matmul weights that ride the weight-only int8 plane
    #: (embeddings are gathers, norms/biases stay f32).
    _WQ_NAMES = ("wq", "wk", "wv", "wqkv", "wo", "w1", "w2")

    @staticmethod
    def _quantize_weight(d, name):
        """Per-output-channel symmetric int8: ``W ≈ Q · s`` with
        ``s = amax(|W|, axis=0) / 127`` — stored as ``<name>`` (int8)
        plus ``<name>__s`` (f32 row vector).  Zero columns quantize
        to zero codes with zero scale, an exact round trip."""
        import jax.numpy as jnp
        w = d.get(name)
        if w is None or w.ndim != 2:
            return
        s = jnp.max(jnp.abs(w), axis=0) / 127.0
        safe = jnp.where(s > 0.0, s, 1.0)
        d[name] = jnp.clip(jnp.round(w / safe), -127,
                           127).astype(jnp.int8)
        d[name + "__s"] = s

    def _lm_params(self):
        """The LM decode-program parameter pytree (embedding, head,
        per-block dicts), built from :meth:`_device_weights` and
        invalidated with it on :meth:`swap_weights` — which is why a
        hot swap re-quantizes automatically: the swapped weights
        rebuild this cache (on the device thread, where every decode
        program runs) under the current weight mode."""
        mode = self._decode_weight_mode()
        cached = self._lm_params_cache
        if cached is None or cached[0] != mode:
            emb, blocks, head = self._lm_chain()
            dev = self._device_weights()
            params = {
                "emb_w": dev[emb["params"]["weights"]],
                "emb_pos": dev[emb["params"]["pos"]],
                "head_w": dev[head["params"]["weights"]],
                "head_b": dev[head["params"]["bias"]]
                if "bias" in head["params"] else None,
                "blocks": [{n: dev[e["params"][n]]
                            for n in e["params"]} for e in blocks],
            }
            if mode == "int8":
                for bp in params["blocks"]:
                    for name in self._WQ_NAMES:
                        self._quantize_weight(bp, name)
                self._quantize_weight(params, "head_w")
            self._lm_params_cache = (mode, params)
        return self._lm_params_cache[1]

    def geometry_of(self):
        """The swap-compatibility fingerprint: the unit table plus
        every weight's shape.  Two artifacts with equal geometry can
        hot-swap weights through the SAME compiled programs."""
        return (self.units,
                {k: tuple(v.shape) for k, v in self.weights.items()})

    def same_geometry(self, other):
        """True when ``other``'s weights can be swapped into this
        model's compiled programs in place."""
        return self.geometry_of() == other.geometry_of()

    def swap_weights(self, new_weights):
        """In-place hot weight swap: replaces every parameter with
        the same-named array from ``new_weights`` and bumps
        :attr:`weight_version`.  The compile cache survives untouched
        — weights are traced arguments, so the cached executables
        simply read the new values on their next call.  Raises
        :class:`Bug` on any geometry mismatch (missing/extra/reshaped
        keys); the caller falls back to a full model replacement
        (drain-and-swap)."""
        new = {k: numpy.asarray(v, dtype=numpy.float32)
               for k, v in new_weights.items()}
        mine = {k: tuple(v.shape) for k, v in self.weights.items()}
        theirs = {k: tuple(v.shape) for k, v in new.items()}
        if mine != theirs:
            missing = sorted(set(mine) - set(theirs))
            extra = sorted(set(theirs) - set(mine))
            reshaped = sorted(
                "%s %s->%s" % (k, mine[k], theirs[k])
                for k in set(mine) & set(theirs)
                if mine[k] != theirs[k])
            raise Bug(
                "weight geometry mismatch — in-place swap impossible"
                " (missing: %s; new: %s; reshaped: %s)" %
                (missing or "-", extra or "-", reshaped or "-"))
        self.weights = new
        self._jax_weights = None
        self._lm_params_cache = None
        self.weight_version += 1
        return self.weight_version

    # ---- numpy reference path (native-runtime mirror) -----------------

    def _shape_input(self, x):
        """Reshapes flat samples to the manifest geometry; a 2-D
        input over a 1-D sample shape of DIFFERENT length passes
        through — token models accept any sequence length (the pos
        table is sliced to fit), e.g. the generation parity tests
        feed growing prefixes."""
        if tuple(x.shape[1:]) == self.input_shape:
            return x
        n = 1
        for d in self.input_shape:
            n *= d
        if x.size == x.shape[0] * n:
            return x.reshape((x.shape[0],) + self.input_shape)
        if x.ndim == 2 and len(self.input_shape) == 1 and \
                self.units and self.units[0]["type"] == "embedding":
            # Token models only: any sequence length is legitimate
            # (the pos table is sliced to fit).  Dense artifacts keep
            # the strict-width check — the numpy path mirrors the
            # native runtime, which rejects wrong-size samples.
            return x
        raise Bug("input shape %s does not fit samples of %s" %
                  (x.shape, self.input_shape))

    def forward_numpy(self, x):
        x = numpy.asarray(x, dtype=numpy.float32)
        x = self._shape_input(x)
        for entry in self.units:
            x = self._run_numpy(entry, x)
        return x

    def _param(self, entry, name):
        return self.weights[entry["params"][name]]

    def _run_numpy(self, entry, x):
        t = entry["type"]
        cfg = entry["config"]
        if t == "mean_disp":
            return (x - self._param(entry, "mean")) * \
                self._param(entry, "rdisp")
        if t == "dropout":
            return x
        if t.startswith("activation_"):
            return _ACTS[t.split("activation_")[1]](x)
        if t.startswith("all2all") or t in ("softmax", "rbm"):
            w = self._param(entry, "weights")
            y = x.reshape(x.shape[0], -1) @ w
            if "bias" in entry["params"]:
                y = y + self._param(entry, "bias")
            y = _ACTS[_DENSE_ACT[t]](y)
            shape = cfg.get("output_sample_shape")
            if shape:
                y = y.reshape((x.shape[0],) + tuple(shape))
            return y
        if t == "kohonen":
            return self._kohonen_numpy(entry, x)
        if t == "embedding":
            w = self._param(entry, "weights")
            # Clamp OOV ids like the native runtime and jax indexing
            # do — the mirror must not raise/wrap where they clamp.
            tokens = numpy.clip(x.astype(numpy.int32), 0,
                                w.shape[0] - 1)
            return (w[tokens] +
                    self._param(entry, "pos")[:tokens.shape[1]]
                    ).astype(numpy.float32)
        if t == "transformer_block":
            return self._transformer_numpy(entry, x)
        if t == "lm_head":
            w = self._param(entry, "weights")
            y = x @ w
            if "bias" in entry["params"]:
                y = y + self._param(entry, "bias")
            return y.astype(numpy.float32)
        if t.startswith("conv"):
            return self._conv_numpy(entry, x)
        if t.endswith("pooling"):
            return self._pool_numpy(entry, x)
        if t == "norm":
            return self._lrn_numpy(cfg, x)
        raise Bug("unknown unit type %r in artifact" % t)

    def _transformer_numpy(self, entry, x):
        """Pre-LN block, numpy mirror of znicz/attention.py
        ``transformer_block_apply``."""
        cfg = entry["config"]
        H = int(cfg["n_heads"])
        causal = bool(cfg.get("causal", 1))
        p = {n: self._param(entry, n) for n in entry["params"]}

        def ln(v, g, b, eps=1e-5):
            mu = v.mean(axis=-1, keepdims=True)
            var = ((v - mu) ** 2).mean(axis=-1, keepdims=True)
            return (v - mu) / numpy.sqrt(var + eps) * g + b

        B, S, E = x.shape
        D = E // H
        h = ln(x, p["ln1_g"], p["ln1_b"])
        if "wqkv" in p:
            # Fused-QKV artifact: one (E, 3E) head-major projection
            # (znicz/attention.fuse_qkv_arrays layout).
            qkv = (h @ p["wqkv"] + p["bqkv"]).reshape(B, S, H, 3, D)
            q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
        else:
            q = (h @ p["wq"] + p["bq"]).reshape(B, S, H, D)
            k = (h @ p["wk"] + p["bk"]).reshape(B, S, H, D)
            v = (h @ p["wv"] + p["bv"]).reshape(B, S, H, D)
        scores = numpy.einsum("bqhd,bkhd->bhqk", q, k) / \
            numpy.sqrt(D)
        if causal:
            mask = numpy.tril(numpy.ones((S, S), bool))
            scores = numpy.where(mask, scores, -1e30)
        scores -= scores.max(axis=-1, keepdims=True)
        pattn = numpy.exp(scores)
        pattn /= pattn.sum(axis=-1, keepdims=True)
        attn = numpy.einsum("bhqk,bkhd->bqhd", pattn, v) \
            .reshape(B, S, E)
        x = x + attn @ p["wo"] + p["bo"]
        h = ln(x, p["ln2_g"], p["ln2_b"])
        h = numpy.maximum(h @ p["w1"] + p["b1"], 0.0)
        return (x + h @ p["w2"] + p["b2"]).astype(numpy.float32)

    def _kohonen_numpy(self, entry, x):
        # Squared distance to each SOM neuron (KohonenForward emits
        # the full distance map; BMU = argmin over the last axis).
        # float64 accumulation: the expanded form cancels near zero
        # exactly where the SOM converged, and the native runtime
        # accumulates exact squared differences in double.
        w = self._param(entry, "weights") \
            .astype(numpy.float64)  # (n_neurons, n_in)
        xf = x.reshape(x.shape[0], -1).astype(numpy.float64)
        return ((xf * xf).sum(1, keepdims=True) - 2.0 * (xf @ w.T) +
                (w * w).sum(1)).astype(numpy.float32)

    def _conv_numpy(self, entry, x):
        cfg = entry["config"]
        w = self._param(entry, "weights")  # HWIO
        ky, kx = w.shape[0], w.shape[1]
        (pt, pb), (pl, pr) = cfg["padding"]
        sh, sw = cfg["sliding"]
        xp = numpy.pad(x, ((0, 0), (pt, pb), (pl, pr), (0, 0)))
        n, H, W, C = xp.shape
        out_h = (H - ky) // sh + 1
        out_w = (W - kx) // sw + 1
        # im2col: patches (n, out_h, out_w, ky*kx*C)
        cols = numpy.empty((n, out_h, out_w, ky * kx * C),
                           dtype=numpy.float32)
        for iy in range(ky):
            for ix in range(kx):
                patch = xp[:, iy:iy + sh * out_h:sh,
                           ix:ix + sw * out_w:sw, :]
                cols[..., (iy * kx + ix) * C:(iy * kx + ix + 1) * C] \
                    = patch
        y = cols @ w.reshape(-1, w.shape[-1])
        if "bias" in entry["params"]:
            y = y + self._param(entry, "bias")
        act = {"conv": "linear", "conv_tanh": "tanh",
               "conv_relu": "softplus", "conv_str": "str",
               "conv_sigmoid": "sigmoid"}[entry["type"]]
        return _ACTS[act](y)

    def _pool_numpy(self, entry, x):
        cfg = entry["config"]
        t = entry["type"]
        ky, kx = int(cfg["ky"]), int(cfg["kx"])
        sh, sw = cfg["sliding"]
        (pt, pb), (pl, pr) = cfg["padding"]
        n, H, W, C = x.shape
        # Ceil-mode output + tail padding (matches Pooling
        # _window_padding).
        out_h = -(-(H + pt + pb - ky) // sh) + 1
        out_w = -(-(W + pl + pr - kx) // sw) + 1
        need_h = (out_h - 1) * sh + ky - (H + pt)
        need_w = (out_w - 1) * sw + kx - (W + pl)
        pb2, pr2 = max(pb, need_h), max(pr, need_w)
        if t == "avg_pooling":
            fill = 0.0
        else:
            fill = numpy.nan  # excluded via nan-aware reductions
        xp = numpy.full((n, H + pt + pb2, W + pl + pr2, C), fill,
                        dtype=numpy.float32)
        xp[:, pt:pt + H, pl:pl + W, :] = x
        y = numpy.empty((n, out_h, out_w, C), dtype=numpy.float32)
        if t == "avg_pooling":
            # Sum over zero-padded windows, divided by the true
            # (unpadded) window population.
            ones = numpy.zeros_like(xp)
            ones[:, pt:pt + H, pl:pl + W, :] = 1.0
        for oy in range(out_h):
            for ox in range(out_w):
                win = xp[:, oy * sh:oy * sh + ky,
                         ox * sw:ox * sw + kx, :]
                flat = win.reshape(n, -1, C)
                if t == "avg_pooling":
                    cnt = ones[:, oy * sh:oy * sh + ky,
                               ox * sw:ox * sw + kx, :] \
                        .reshape(n, -1, C).sum(axis=1)
                    y[:, oy, ox] = flat.sum(axis=1) / \
                        numpy.maximum(cnt, 1.0)
                elif t == "maxabs_pooling":
                    # nan→-inf (not nanargmax: an all-padding window
                    # must yield NaN, matching the native runtime,
                    # rather than raise on the all-NaN slice).
                    absf = numpy.where(numpy.isnan(flat),
                                       -numpy.inf, numpy.abs(flat))
                    idx = absf.argmax(axis=1)
                    y[:, oy, ox] = numpy.take_along_axis(
                        flat, idx[:, None, :], axis=1)[:, 0]
                else:
                    y[:, oy, ox] = numpy.nanmax(flat, axis=1)
        return y

    @staticmethod
    def _lrn_numpy(cfg, x):
        alpha, beta, k, n = (cfg["alpha"], cfg["beta"], cfg["k"],
                             int(cfg["n"]))
        c = x.shape[-1]
        half = n // 2
        sq = x * x
        ssum = numpy.zeros_like(x)
        for j in range(c):
            lo, hi = max(0, j - half), min(c, j + (n - 1 - half) + 1)
            ssum[..., j] = sq[..., lo:hi].sum(axis=-1)
        return x / (k + (alpha / n) * ssum) ** beta

    # ---- jax serving path ---------------------------------------------

    def forward(self, x):
        """Jitted jax forward (compiles once per batch shape; the
        weights ride as a traced pytree argument so a hot swap reuses
        the compiled executable)."""
        import jax
        if self._jit_forward is None:
            def model_forward(weights, x):
                return self._jax_chain(x, weights)
            self._jit_forward = jax.jit(model_forward)
        return numpy.asarray(self._jit_forward(
            self._device_weights(),
            numpy.asarray(x, dtype=numpy.float32)))

    def forward_bucketed(self, x, batch_bucket):
        """Serving forward with the batch dim padded up to
        ``batch_bucket`` (zeros — rows are independent, pad outputs
        are dropped), so the compile-key set the serving layer can
        reach is the bucket grid, not every client batch size.  Shape
        sentinels ride the LRU compile cache for hit/miss accounting
        and the hard entry cap (eviction resets the forward jit)."""
        x = numpy.asarray(x, dtype=numpy.float32)
        if x.ndim == 1:
            x = x[None]
        n = x.shape[0]
        batch_bucket = max(int(batch_bucket), n)
        if batch_bucket > n:
            x = numpy.concatenate(
                [x, numpy.zeros((batch_bucket - n,) + x.shape[1:],
                                numpy.float32)], axis=0)
        self.compile_cache.get_or_build(
            ("fwd",) + tuple(x.shape), lambda: True)
        return self.forward(x)[:n]

    @staticmethod
    def _serving_attend(causal):
        """The serving attention: f32 intermediates, XLA formulation
        — PINNED, regardless of the attention fast-path knobs.  A
        training process flipping ``attention_dtype``/``kernel``
        must never change deployed bits (greedy decode is promised
        bit-stable); the fast path reaches serving only through an
        explicit future gate, not a global knob."""
        import functools
        from .ops.attention import attention
        return functools.partial(attention, causal=causal,
                                 precision="f32", kernel="xla")

    @staticmethod
    def _decode_kernel_mode():
        """The ONE explicit gate through which the attention fast
        path may reach serving: ``root.common.engine.decode_kernel``
        ("off" default — the f32/xla pin stands until the decode
        kernel's token-identity gate passes on the target platform).
        "pallas"/"auto" engage the flash-decode kernel on a TPU where
        the geometry allows; "interpret" forces the
        interpret-mode kernel (the CPU token-identity tests — never
        a production setting)."""
        from .config import root, get as config_get
        mode = str(config_get(root.common.engine.decode_kernel,
                              "off"))
        if mode not in ("off", "pallas", "auto", "interpret"):
            raise Bug("unknown decode kernel mode %r — valid: off, "
                      "pallas, auto, interpret" % (mode,))
        return mode

    @classmethod
    def _decode_attend(cls):
        """None (the dense inline math) unless the decode-kernel
        gate is on; otherwise an ``attend(q, kc, vc, key_mask)``
        hook — the serving twin of the training path's ``attend=``
        override — that returns the flash-decode result, or None
        when the traced shapes sit outside the decode contract
        (prefill chunks, odd geometry) so the caller's dense
        formulation proceeds unchanged.  Resolved at program BUILD
        time; the mode string rides every decode compile-cache key,
        so flipping the knob can never serve a stale executable."""
        mode = cls._decode_kernel_mode()
        if mode == "off":
            return None
        import jax.numpy as jnp
        from .ops import pallas_attention as PA
        from .backends import tpu_available
        interpret = mode == "interpret"

        def attend(q, kc, vc, key_mask, k_scale=None, v_scale=None):
            if not PA.supports_decode(q.shape, kc.shape,
                                      interpret=interpret):
                return None
            if not interpret and not tpu_available():
                return None
            # f32 operands: the serving surfaces promise f32 math —
            # the kernel changes the REDUCTION ORDER only, which the
            # token-identity gate covers.  On a quantized pool the
            # k/v arrive as stored codes plus per-position scales and
            # the DEQUANT HAPPENS INSIDE THE KERNEL's k/v gather —
            # the dequantized cache is never materialized in HBM.
            return PA.pallas_decode_attention(
                q, kc, vc, key_mask, operand_dtype=jnp.float32,
                interpret=interpret, k_scale=k_scale,
                v_scale=v_scale)

        return attend

    def _jax_chain(self, x, weights=None):
        """The traced forward chain.  ``weights`` is the pytree the
        jit passes as an ARGUMENT (hot-swappable); None falls back to
        the host dict for direct/debug calls."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        if weights is None:
            weights = self.weights

        def par(entry, name):
            return weights[entry["params"][name]]

        x = self._shape_input(x)
        for entry in self.units:
            t = entry["type"]
            cfg = entry["config"]
            if t == "mean_disp":
                x = (x - par(entry, "mean")) * par(entry, "rdisp")
            elif t == "dropout":
                pass
            elif t.startswith("activation_"):
                x = _jax_act(t.split("activation_")[1], x)
            elif t.startswith("all2all") or t in ("softmax", "rbm"):
                w = par(entry, "weights")
                y = x.reshape(x.shape[0], -1) @ w
                if "bias" in entry["params"]:
                    y = y + par(entry, "bias")
                x = _jax_act(_DENSE_ACT[t], y)
                shape = cfg.get("output_sample_shape")
                if shape:
                    x = x.reshape((x.shape[0],) + tuple(shape))
            elif t == "embedding":
                w = jnp.asarray(par(entry, "weights"))
                # Explicit clamp: jnp indexing wraps negatives where
                # the native runtime (and the numpy mirror) clamp.
                tokens = jnp.clip(x.astype(jnp.int32), 0,
                                  w.shape[0] - 1)
                x = (w[tokens] +
                     par(entry, "pos")[:tokens.shape[1]])
            elif t == "transformer_block":
                from .znicz.attention import transformer_block_apply
                p = {n: par(entry, n) for n in entry["params"]}
                x = transformer_block_apply(
                    p, x, int(cfg["n_heads"]),  # lint-ok: VL101 manifest int
                    bool(cfg.get("causal", 1)), jnp.float32,
                    attend=self._serving_attend(
                        bool(cfg.get("causal", 1))))
            elif t == "lm_head":
                w = par(entry, "weights")
                y = x @ w
                if "bias" in entry["params"]:
                    y = y + par(entry, "bias")
                x = y
            elif t == "kohonen":
                w = par(entry, "weights")
                xf = x.reshape(x.shape[0], -1)
                # Expanded ‖x−w‖² cancels catastrophically under the
                # TPU's default bf16-input matmul — distances sit near
                # zero exactly where the SOM converged. Force full f32.
                xw = lax.dot(xf, w.T,
                             precision=jax.lax.Precision.HIGHEST)
                x = ((xf * xf).sum(1, keepdims=True) - 2.0 * xw +
                     (w * w).sum(1))
            elif t.startswith("conv"):
                w = par(entry, "weights")
                y = lax.conv_general_dilated(
                    x, w, window_strides=tuple(cfg["sliding"]),
                    padding=tuple(tuple(p) for p in cfg["padding"]),
                    dimension_numbers=("NHWC", "HWIO", "NHWC"))
                if "bias" in entry["params"]:
                    y = y + par(entry, "bias")
                act = {"conv": "linear", "conv_tanh": "tanh",
                       "conv_relu": "softplus", "conv_str": "str",
                       "conv_sigmoid": "sigmoid"}[t]
                x = _jax_act(act, y)
            elif t.endswith("pooling"):
                x = self._jax_pool(t, cfg, x)
            elif t == "norm":
                c = x.shape[-1]
                half = int(cfg["n"]) // 2  # lint-ok: VL101 manifest int
                i = jnp.arange(c)
                d = i[:, None] - i[None, :]
                band = ((d >= -half) &
                        # lint-ok: VL101 manifest int
                        (d <= int(cfg["n"]) - 1 - half)
                        ).astype(jnp.float32)
                ssum = jnp.einsum("...c,cd->...d", x * x, band)
                x = x / (cfg["k"] + (cfg["alpha"] / cfg["n"]) *
                         ssum) ** cfg["beta"]
            else:
                raise Bug("unknown unit type %r" % t)
        return x

    # ---- autoregressive generation (KV cache) -------------------------

    def _lm_chain(self):
        """(embedding, [blocks], lm_head) entries, or Bug when the
        artifact is not a causal LM.  Dropout entries are inert at
        inference and skipped."""
        entries = [e for e in self.units if e["type"] != "dropout"]
        if len(entries) < 3 or entries[0]["type"] != "embedding" or \
                entries[-1]["type"] != "lm_head" or \
                any(e["type"] != "transformer_block"
                    for e in entries[1:-1]):
            raise Bug(
                "generate() needs an embedding → transformer_block* "
                "→ lm_head chain; artifact has %s" %
                [e["type"] for e in self.units])
        for e in entries[1:-1]:
            if not e["config"].get("causal", 1):
                raise Bug("generate() requires causal attention "
                          "(block %s is bidirectional)" % e["name"])
        return entries[0], entries[1:-1], entries[-1]

    def _cached_block(self, p, x, ck, cv, start, n_heads,
                      key_mask=None, attend=None):
        """One pre-LN block over a chunk of positions
        [start, start+s) with a (B, L, H, D) KV cache: the chunk's
        k/v are written into the cache, queries attend the WHOLE
        cache under the global causal mask (unfilled positions are
        in the masked future by construction).  Used for BOTH
        prefill (s = prompt length, start = 0) and incremental
        decode (s = 1) — one code path, so prefill/decode parity is
        structural.

        ``key_mask`` (B, S_, L) overrides the causal mask with a
        per-BATCH-ELEMENT valid-key mask — the bucketed serving path
        uses it to exclude each row's pad slots, so coalesced
        requests of different true lengths cannot see each other's
        padding (attention is permutation-invariant over key slots:
        masking pads and keeping logical positions in the embeddings
        reproduces the unpadded computation exactly).

        ``attend`` (the :meth:`_decode_attend` hook): when set AND it
        accepts the traced shapes, attention runs through the
        flash-decode kernel instead of the dense einsums — the SAME
        mask, so masked slots stay exact zeros; it returns None for
        out-of-contract shapes (prefills) and the dense path below
        proceeds untouched."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        def ln(v, g, b, eps=1e-5):
            mu = v.mean(axis=-1, keepdims=True)
            var = ((v - mu) ** 2).mean(axis=-1, keepdims=True)
            return (v - mu) * jnp.reciprocal(jnp.sqrt(var + eps)) \
                * g + b

        B, S_, E = x.shape
        H = n_heads
        D = E // H
        L = ck.shape[1]
        h = ln(x, p["ln1_g"], p["ln1_b"])
        if "wqkv" in p:
            # Fused-QKV artifact: same head-major (E, 3E) layout as
            # the training/serving forward paths.
            qkv = (_mm(h, p, "wqkv") +
                   p["bqkv"]).reshape(B, S_, H, 3, D)
            q, kn, vn = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
        else:
            q = (_mm(h, p, "wq") + p["bq"]).reshape(B, S_, H, D)
            kn = (_mm(h, p, "wk") + p["bk"]).reshape(B, S_, H, D)
            vn = (_mm(h, p, "wv") + p["bv"]).reshape(B, S_, H, D)
        ck = lax.dynamic_update_slice(ck, kn, (0, start, 0, 0))
        cv = lax.dynamic_update_slice(cv, vn, (0, start, 0, 0))
        if key_mask is None:
            qpos = start + jnp.arange(S_)
            kmask = jnp.broadcast_to(
                (qpos[:, None] >= jnp.arange(L)[None, :])[None],
                (B, S_, L))
        else:
            kmask = key_mask
        attn = attend(q, ck, cv, kmask) if attend is not None \
            else None
        if attn is None:
            scores = jnp.einsum(
                "bqhd,bkhd->bqhk", q, ck,
                preferred_element_type=jnp.float32) / (D ** 0.5)
            scores = jnp.where(kmask[:, :, None, :], scores, -1e30)
            w = jax.nn.softmax(scores, axis=-1)
            attn = jnp.einsum("bqhk,bkhd->bqhd", w, cv)
        x = x + _mm(attn.reshape(B, S_, E), p, "wo") + p["bo"]
        h = ln(x, p["ln2_g"], p["ln2_b"])
        x = x + _mm(jnp.maximum(_mm(h, p, "w1") + p["b1"], 0.0),
                    p, "w2") + p["b2"]
        return x.astype(jnp.float32), ck, cv

    def _build_generate(self, S0, max_new):
        """Jitted (prompt, key) → (tokens, step_logits): prefill the
        KV caches over the prompt in one batched pass, then lax.scan
        one-token decode steps — each step touches O(L) cache, never
        O(L²) scores, the KV-cache deployment contract."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        emb, blocks, head = self._lm_chain()
        n_heads = [int(e["config"]["n_heads"]) for e in blocks]
        # Static geometry from the weights AT BUILD TIME; the weight
        # VALUES arrive as a traced pytree argument per call, so a
        # same-geometry hot swap rides this compiled program.
        P, E = self.weights[emb["params"]["pos"]].shape
        V = self.weights[emb["params"]["weights"]].shape[0]
        L = S0 + max_new
        if L > P:
            raise Bug(
                "prompt %d + %d new tokens exceeds the model's "
                "positional table (%d)" % (S0, max_new, P))

        def embed(params, tokens, start):
            t = jnp.clip(tokens.astype(jnp.int32), 0, V - 1)
            pos = lax.dynamic_slice(params["emb_pos"], (start, 0),
                                    (t.shape[1], E))
            return params["emb_w"][t] + pos

        def logits_of(params, x_last):
            return _head_logits(x_last, params["head_w"],
                                params["head_b"],
                                params.get("head_w__s"))

        def sample(logits, key, temperature):
            """Greedy/temperature select with temperature as a TRACED
            scalar — it must not be a compile-cache key (a serving
            client could otherwise force a fresh multi-second jit per
            distinct float)."""
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            sampled = jax.random.categorical(
                key, logits / jnp.maximum(temperature, 1e-6),
                axis=-1).astype(jnp.int32)
            return jnp.where(temperature > 0.0, sampled, greedy)

        att = self._decode_attend()

        def lm_generate(params, prompt, key, temperature):
            B = prompt.shape[0]
            block_params = params["blocks"]
            x = embed(params, prompt, 0)
            caches = []
            for p, H in zip(block_params, n_heads):
                ck = jnp.zeros((B, L, H, E // H), jnp.float32)
                cv = jnp.zeros((B, L, H, E // H), jnp.float32)
                x, ck, cv = self._cached_block(p, x, ck, cv, 0, H,
                                               attend=att)
                caches.append((ck, cv))
            first_logits = logits_of(params, x[:, -1])
            tok0 = sample(first_logits, jax.random.fold_in(key, 0),
                          temperature)

            def body(carry, j):
                prev_tok, caches = carry
                t = S0 + j  # position the previous token occupies
                x = embed(params, prev_tok[:, None], t)
                new_caches = []
                for (ck, cv), p, H in zip(caches, block_params,
                                          n_heads):
                    x, ck, cv = self._cached_block(p, x, ck, cv, t, H,
                                                   attend=att)
                    new_caches.append((ck, cv))
                logits = logits_of(params, x[:, 0])
                tok = sample(logits, jax.random.fold_in(key, j + 1),
                             temperature)
                return (tok, new_caches), (prev_tok, logits)

            if max_new > 1:
                (last_tok, _), (toks, step_logits) = lax.scan(
                    body, (tok0, caches), jnp.arange(max_new - 1))
                tokens = jnp.concatenate(
                    [toks.swapaxes(0, 1), last_tok[:, None]], axis=1)
                all_logits = jnp.concatenate(
                    [first_logits[:, None],
                     step_logits.swapaxes(0, 1)], axis=1)
            else:
                tokens = tok0[:, None]
                all_logits = first_logits[:, None]
            return tokens, all_logits

        return jax.jit(lm_generate)

    def generate(self, prompt, max_new_tokens, temperature=0.0,
                 seed=0, return_logits=False):
        """Autoregressive decoding from the artifact: greedy when
        ``temperature`` == 0, else temperature sampling.  Returns the
        (B, prompt+new) token array — with ``return_logits``, also
        the (B, new, V) pre-sampling logits (what the parity tests
        compare against the full forward).  Prompt lengths round up
        to a power-of-two bucket and ride the padded
        ``generate_bucketed`` program (greedy output is bit-identical
        — the bucketed parity gate), so a serving workload of
        arbitrary lengths compiles O(log S) programs, one per bucket
        — temperature stays a TRACED input, deliberately excluded
        from the compile-cache key (a serving client could otherwise
        force a fresh multi-second jit per distinct float); the KV
        cache makes each decode step O(L·E) instead of re-running the
        full O(L²) forward (the incremental-serving obligation the
        reference's RESTful role implies, restful_api.py:78)."""
        import jax
        import jax.numpy as jnp
        prompt = numpy.atleast_2d(
            numpy.asarray(prompt, dtype=numpy.int32))
        if prompt.shape[1] < 1:
            raise Bug("prompt must contain at least one token")
        if max_new_tokens < 1:
            raise Bug("max_new_tokens must be >= 1")
        temperature = float(temperature)
        if not numpy.isfinite(temperature) or temperature < 0.0:
            raise Bug("temperature must be finite and >= 0")
        S0, max_new = prompt.shape[1], int(max_new_tokens)
        limit = self.max_position
        if limit is not None and S0 + max_new > limit:
            raise Bug(
                "prompt %d + %d new tokens exceeds the model's "
                "positional table (%d)" % (S0, max_new, limit))
        if not return_logits:
            # Decode-serving compile policy: round the prompt length
            # up to a power-of-two bucket and ride the padded
            # ``generate_bucketed`` path (greedy decode is
            # bit-identical by the bucketed-parity gate), so a
            # workload of arbitrary prompt lengths compiles O(log S)
            # programs instead of one per distinct length.  The
            # ``return_logits`` debugging path keeps the exact-length
            # program (what the parity tests pin).
            from .serving.buckets import bucket_of
            B = prompt.shape[0]
            S0b = bucket_of(S0, floor=16, cap=limit)
            padded = numpy.zeros((B, S0b), dtype=numpy.int32)
            padded[:, :S0] = prompt
            # Per-row seeds: generate_bucketed folds a PRNG key per
            # row, so a broadcast scalar would sample every row from
            # the same stream (identical prompts → identical
            # continuations at temperature > 0).  Greedy ignores the
            # seed entirely, so this keeps the bit-identical gate.
            gen = self.generate_bucketed(
                padded, numpy.full(B, S0, dtype=numpy.int32),
                max_new, temperatures=temperature,
                seeds=(int(seed) + numpy.arange(B)) & 0xFFFFFFFF)
            return numpy.concatenate([prompt, gen], axis=1)
        # Compile cache keyed ONLY by geometry (temperature is a
        # traced input), bounded LRU — the key is client-reachable
        # through the serving endpoint, so it must not grow without
        # bound.
        fn = self.compile_cache.get_or_build(
            ("gen", S0, max_new, self._decode_weight_mode(),
             self._decode_kernel_mode()),
            lambda: self._build_generate(S0, max_new))
        tokens, logits = fn(self._lm_params(), prompt,
                            jax.random.PRNGKey(seed),
                            jnp.float32(temperature))
        tokens = numpy.asarray(tokens)
        full = numpy.concatenate([prompt, tokens], axis=1)
        if return_logits:
            return full, numpy.asarray(logits)
        return full

    # ---- shape-bucketed serving decode --------------------------------

    def _build_generate_bucketed(self, S0b, max_new):
        """Jitted (prompts, lengths, seeds, temperatures) → generated
        tokens for a PADDED prompt bucket: prompts are right-padded
        to ``S0b`` columns and each row carries its true length.

        Exactness argument (what makes coalescing different-length
        requests safe): right-padding keeps every real prompt token
        at its true position 0..len-1, so prefill under the plain
        causal mask is bit-identical for real positions; the first
        logits are gathered per row at position len-1; each decode
        step embeds the new token at its LOGICAL position (len+j,
        per row) while writing its K/V into the uniform cache slot
        S0b+j, and the per-row key mask admits exactly {real prompt
        slots} ∪ {generated slots so far}.  Attention is permutation-
        invariant over key slots, so excluding pad slots and keeping
        logical positions reproduces the unpadded computation
        exactly — greedy decode matches ``generate()`` bit-for-bit.
        (Sampling draws per-ROW keys here — deterministic per seed,
        but a different stream than the single-key batch draw of
        ``generate()``.)"""
        import jax
        import jax.numpy as jnp
        from jax import lax
        emb, blocks, head = self._lm_chain()
        n_heads = [int(e["config"]["n_heads"]) for e in blocks]
        P, E = self.weights[emb["params"]["pos"]].shape
        V = self.weights[emb["params"]["weights"]].shape[0]
        if S0b > P:
            raise Bug("prompt bucket %d exceeds the model's "
                      "positional table (%d)" % (S0b, P))
        L = S0b + max_new

        def logits_of(params, x_last):
            return _head_logits(x_last, params["head_w"],
                                params["head_b"],
                                params.get("head_w__s"))

        sample_rows = _sample_rows
        att = self._decode_attend()

        def lm_generate_bucketed(params, prompts, lengths, seeds, temps):
            B = prompts.shape[0]
            emb_w = params["emb_w"]
            emb_pos = params["emb_pos"]
            block_params = params["blocks"]
            keys0 = jax.vmap(jax.random.PRNGKey)(seeds)
            t = jnp.clip(prompts.astype(jnp.int32), 0, V - 1)
            x = emb_w[t] + emb_pos[:S0b]
            caches = []
            for p, H in zip(block_params, n_heads):
                ck = jnp.zeros((B, L, H, E // H), jnp.float32)
                cv = jnp.zeros((B, L, H, E // H), jnp.float32)
                x, ck, cv = self._cached_block(p, x, ck, cv, 0, H,
                                               attend=att)
                caches.append((ck, cv))
            idx = jnp.clip(lengths - 1, 0, S0b - 1)
            first_logits = logits_of(params, x[jnp.arange(B), idx])
            tok0 = sample_rows(
                first_logits,
                jax.vmap(lambda k: jax.random.fold_in(k, 0))(keys0),
                temps)
            slots = jnp.arange(L)

            def body(carry, j):
                prev_tok, caches = carry
                slot = S0b + j
                # Logical position (len+j per row) for the embedding;
                # clipped so bucket-overrun junk steps (a neighbor in
                # the batch wanted more tokens) read in-bounds and
                # stay discardable instead of faulting.
                posn = jnp.clip(lengths + j, 0, P - 1)
                pe = jnp.take(emb_pos, posn, axis=0)
                xj = emb_w[jnp.clip(prev_tok, 0, V - 1)][:, None] \
                    + pe[:, None]
                kmask = ((slots[None, :] < lengths[:, None]) |
                         ((slots[None, :] >= S0b) &
                          (slots[None, :] <= slot)))[:, None, :]
                new_caches = []
                for (ck, cv), p, H in zip(caches, block_params,
                                          n_heads):
                    xj, ck, cv = self._cached_block(
                        p, xj, ck, cv, slot, H, key_mask=kmask,
                        attend=att)
                    new_caches.append((ck, cv))
                logits = logits_of(params, xj[:, 0])
                tok = sample_rows(
                    logits,
                    jax.vmap(lambda k: jax.random.fold_in(k, j + 1))(
                        keys0),
                    temps)
                return (tok, new_caches), prev_tok

            if max_new > 1:
                (last_tok, _), toks = lax.scan(
                    body, (tok0, caches), jnp.arange(max_new - 1))
                return jnp.concatenate(
                    [toks.swapaxes(0, 1), last_tok[:, None]], axis=1)
            return tok0[:, None]

        return jax.jit(lm_generate_bucketed)

    def generate_bucketed(self, prompts, lengths, max_new_tokens,
                          temperatures=0.0, seeds=0):
        """The serving engine's coalesced decode entry point:
        ``prompts`` (B, S0b) right-padded int32, ``lengths`` (B,)
        true prompt lengths, scalar-or-(B,) ``temperatures`` /
        ``seeds``.  Returns the (B, max_new_tokens) GENERATED tokens
        (the caller holds the true prompts).  Compiles once per
        (B, S0b, max_new_tokens) bucket triple — with power-of-two
        bucketing upstream the reachable key set is O(log² span),
        hard-capped by the LRU compile cache."""
        prompts = numpy.atleast_2d(
            numpy.asarray(prompts, dtype=numpy.int32))
        B, S0b = prompts.shape
        lengths = numpy.asarray(lengths, dtype=numpy.int32)
        if lengths.shape != (B,):
            raise Bug("lengths shape %s does not match batch %d" %
                      (lengths.shape, B))
        if S0b < 1 or (lengths < 1).any() or (lengths > S0b).any():
            raise Bug("prompt lengths must lie in [1, %d]" % S0b)
        max_new = int(max_new_tokens)
        if max_new < 1:
            raise Bug("max_new_tokens must be >= 1")
        temps = numpy.ascontiguousarray(numpy.broadcast_to(
            numpy.asarray(temperatures, numpy.float32), (B,)))
        if not numpy.isfinite(temps).all() or (temps < 0.0).any():
            raise Bug("temperature must be finite and >= 0")
        seeds = numpy.ascontiguousarray(numpy.broadcast_to(
            numpy.asarray(seeds, numpy.uint32), (B,)))
        limit = self.max_position
        # The bucket must fit the positional table (prefill embeds
        # 0..S0b-1) and every row must have room for at least one
        # generated token.  max_new is a BUCKET, deliberately not
        # validated against the table: decode steps whose logical
        # position would overrun it read clamped embeddings and
        # produce junk a caller slices away — the serving engine
        # validates each request's TRUE (len + max_new) eagerly, so
        # one long-decode neighbor cannot 400 a whole coalesced
        # batch.
        if limit is not None and (S0b > limit or
                                  int(lengths.max()) >= limit):
            raise Bug(
                "prompt of %d tokens exceeds the model's positional "
                "table (%d)" % (max(S0b, int(lengths.max())), limit))
        fn = self.compile_cache.get_or_build(
            ("genb", B, S0b, max_new, self._decode_weight_mode(),
             self._decode_kernel_mode()),
            lambda: self._build_generate_bucketed(S0b, max_new))
        return numpy.asarray(fn(self._lm_params(), prompts, lengths,
                                seeds, temps))

    # ---- paged serving decode (block-pool KV cache) -------------------

    def _paged_geometry(self):
        """(n_layers, n_heads, head_dim) of the LM chain, or Bug —
        the paged pool stacks every layer's blocks in one per-layer
        tensor list, so the head geometry must be uniform."""
        emb, blocks, _ = self._lm_chain()
        heads = {int(e["config"]["n_heads"]) for e in blocks}
        if len(heads) != 1:
            raise Bug("paged decode requires a uniform head count "
                      "across blocks, got %s" % sorted(heads))
        H = heads.pop()
        E = int(self.weights[emb["params"]["weights"]].shape[1])
        if E % H:
            raise Bug("embed dim %d not divisible by %d heads" %
                      (E, H))
        return len(blocks), H, E // H

    def make_kv_pool(self, n_blocks, block_size=16, kv_dtype=None):
        """A :class:`KVBlockPool` backed by per-layer device tensors
        of ``(n_blocks, block_size, H, D)`` k/v blocks — the paged
        substrate the serving engine's decode-step batching runs on.
        ``kv_dtype`` picks the storage plane (default: the
        ``root.common.serving.kv_dtype`` config, "f32"): "f32" is
        byte-for-byte today's exact path, "bf16" a scale-free cast,
        "int8"/"fp8" carry per-(block, head) f32 scale tensors
        alongside the blocks and quantize on scatter / dequantize on
        gather.  Raises Bug when the artifact is not a causal LM."""
        import jax.numpy as jnp
        from .config import root, get as config_get
        if kv_dtype is None:
            kv_dtype = config_get(root.common.serving.kv_dtype,
                                  "f32")
        kv_dtype = check_kv_dtype(kv_dtype)
        L, H, D = self._paged_geometry()
        n, bs = int(n_blocks), int(block_size)
        sd = _kv_storage_jnp(kv_dtype)
        ks = [jnp.zeros((n, bs, H, D), sd) for _ in range(L)]
        vs = [jnp.zeros((n, bs, H, D), sd) for _ in range(L)]
        block_bytes = 2 * L * bs * H * D * _KV_ITEMSIZE[kv_dtype]
        if _KV_QMAX[kv_dtype] is not None:
            sks = [jnp.zeros((n, H), jnp.float32) for _ in range(L)]
            svs = [jnp.zeros((n, H), jnp.float32) for _ in range(L)]
            storage = (ks, vs, sks, svs)
            block_bytes += 2 * L * H * 4
        else:
            storage = (ks, vs)
        return KVBlockPool(n_blocks, block_size, storage=storage,
                           copy_fn=self._kv_copy_block,
                           kv_dtype=kv_dtype,
                           block_bytes=block_bytes)

    def _kv_copy_block(self, storage, src, dst):
        """Device-side block copy for the pool's copy-on-write (one
        jitted program per pool geometry; src/dst are traced, so
        every copy rides the same executable).  On a quantized pool
        the per-(block, head) scale rows copy WITH the codes — the
        copy is bit-exact, so a COW'd block dequantizes to exactly
        the shared original's values."""
        import jax
        ks, vs, sks, svs = _kv_unpack(storage)
        key = ("pcopy", ks[0].shape[0], ks[0].shape[1], len(ks),
               sks is not None)

        def build():
            if sks is None:
                def kv_copy(ks, vs, src, dst):
                    ks = [k.at[dst].set(k[src]) for k in ks]
                    vs = [v.at[dst].set(v[src]) for v in vs]
                    return ks, vs
                return jax.jit(kv_copy, donate_argnums=(0, 1))

            def kv_copy_quant(ks, vs, sks, svs, src, dst):
                ks = [k.at[dst].set(k[src]) for k in ks]
                vs = [v.at[dst].set(v[src]) for v in vs]
                sks = [s.at[dst].set(s[src]) for s in sks]
                svs = [s.at[dst].set(s[src]) for s in svs]
                return ks, vs, sks, svs
            return jax.jit(kv_copy_quant, donate_argnums=(0, 1, 2, 3))

        fn = self.compile_cache.get_or_build(key, build)
        src_dst = jax.device_put((numpy.int32(src),
                                  numpy.int32(dst)))
        if sks is None:
            return fn(ks, vs, *src_dst)
        return fn(ks, vs, sks, svs, *src_dst)

    def export_kv_blocks(self, pool, ids):
        """The addressed pool blocks as ONE host array ``(L, 2, n,
        block_size, H, D)`` f32 (k then v per layer) — the tensor the
        disaggregation wire ships (``serving.fabric.disagg`` frames
        it zero-copy via ``encode_tensor_parts``).  Quantized pools
        DEQUANTIZE on export, so the wire format is
        storage-dtype-agnostic: an int8 prefill worker can feed an
        f32 decode replica and vice versa.  The caller holds refs on
        ``ids`` (``export_prefix_blocks``) so the device rows cannot
        be reused mid-read."""
        import jax.numpy as jnp
        idx = numpy.asarray(list(ids), dtype=numpy.int32)
        ks, vs, sks, svs = _kv_unpack(pool.storage)
        out = []
        for i, (k, v) in enumerate(zip(ks, vs)):
            kb = k[idx].astype(jnp.float32)
            vb = v[idx].astype(jnp.float32)
            if sks is not None:
                kb = kb * sks[i][idx][:, None, :, None]
                vb = vb * svs[i][idx][:, None, :, None]
            out.append(numpy.stack([numpy.asarray(kb),
                                    numpy.asarray(vb)]))
        return numpy.stack(out)

    def import_kv_blocks(self, pool, ids, blocks):
        """Scatters a shipped ``(L, 2, n, block_size, H, D)`` host
        array (from :meth:`export_kv_blocks` on the peer) into THIS
        pool's storage at ``ids`` — re-quantizing with fresh
        per-(block, head) scales when this pool is int8/fp8 (the
        wire is always f32).  Produces new per-layer device tensors
        functionally, exactly like the COW copy — callers on the
        serving path route through the engine's device-thread op
        queue so the write never races a donated decode step."""
        import jax.numpy as jnp
        blocks = numpy.asarray(blocks, dtype=numpy.float32)
        idx = jnp.asarray(list(ids), dtype=jnp.int32)
        ks, vs, sks, svs = _kv_unpack(pool.storage)
        L = len(ks)
        if blocks.shape[:2] != (L, 2) or \
                blocks.shape[2] != len(ids) or \
                blocks.shape[3:] != ks[0].shape[1:]:
            raise Bug("imported KV block shape %s does not match "
                      "pool geometry (L=%d, block=%s, n=%d)" %
                      (blocks.shape, L, ks[0].shape[1:], len(ids)))
        if sks is None:
            ks = [k.at[idx].set(
                jnp.asarray(blocks[i, 0]).astype(k.dtype))
                for i, k in enumerate(ks)]
            vs = [v.at[idx].set(
                jnp.asarray(blocks[i, 1]).astype(v.dtype))
                for i, v in enumerate(vs)]
            pool.storage = (ks, vs)
            return
        qmax = _KV_QMAX[pool.kv_dtype]
        new_ks, new_vs, new_sks, new_svs = [], [], [], []
        for i in range(L):
            kb = jnp.asarray(blocks[i, 0])  # (n, bs, H, D)
            vb = jnp.asarray(blocks[i, 1])
            sk = jnp.max(jnp.abs(kb), axis=(1, 3)) / qmax  # (n, H)
            sv = jnp.max(jnp.abs(vb), axis=(1, 3)) / qmax
            qk = _kv_quantize(kb, sk[:, None, :, None],
                              pool.kv_dtype)
            qv = _kv_quantize(vb, sv[:, None, :, None],
                              pool.kv_dtype)
            new_ks.append(ks[i].at[idx].set(qk))
            new_vs.append(vs[i].at[idx].set(qv))
            new_sks.append(sks[i].at[idx].set(sk))
            new_svs.append(svs[i].at[idx].set(sv))
        pool.storage = (new_ks, new_vs, new_sks, new_svs)

    def _paged_block(self, p, x, pk, pv, tables, wblock, wslot,
                     key_mask, n_heads, attend=None, sk=None,
                     sv=None, kv_dtype="f32"):
        """One pre-LN block against the POOLED cache: the chunk's
        k/v scatter to ``(wblock, wslot)`` (physical block, in-block
        slot — per row AND per chunk position, so rows at different
        sequence positions coexist in one static-shape batch), then
        the whole table is gathered back ``(B, T·bs, H, D)`` and
        queries attend it under ``key_mask``.  Same arithmetic as
        :meth:`_cached_block` — masked slots are exact zeros after
        softmax and real keys keep their relative order, so paged
        greedy decode is bit-identical to the dense cached path.
        ``attend`` is the flag-gated flash-decode hook, exactly as
        in :meth:`_cached_block` (same mask, same zeros).

        QUANTIZED pools (``sk``/``sv``: per-(block, head) f32 scale
        tensors): the quantize happens INSIDE this scatter — the
        written blocks' scales grow monotonically (scatter-max over
        the chunk's |k|,|v| amax), only the written blocks get their
        stored codes rescaled by old/new (an untouched block's ratio
        is EXACTLY 1.0, an exact code round trip — which is why a
        shared prefix block, never written by a reader, stays
        bit-stable under COW/refcount semantics), and the chunk's
        values quantize at the grown scale.  The gather dequantizes:
        either inside the flash-decode kernel (codes + per-position
        scales feed ``attend``) or as ``codes·scale`` for the dense
        fallback einsum."""
        import jax
        import jax.numpy as jnp

        def ln(v, g, b, eps=1e-5):
            mu = v.mean(axis=-1, keepdims=True)
            var = ((v - mu) ** 2).mean(axis=-1, keepdims=True)
            return (v - mu) * jnp.reciprocal(jnp.sqrt(var + eps)) \
                * g + b

        B, S_, E = x.shape
        H = n_heads
        D = E // H
        h = ln(x, p["ln1_g"], p["ln1_b"])
        if "wqkv" in p:
            qkv = (_mm(h, p, "wqkv") +
                   p["bqkv"]).reshape(B, S_, H, 3, D)
            q, kn, vn = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
        else:
            q = (_mm(h, p, "wq") + p["bq"]).reshape(B, S_, H, D)
            kn = (_mm(h, p, "wk") + p["bk"]).reshape(B, S_, H, D)
            vn = (_mm(h, p, "wv") + p["bv"]).reshape(B, S_, H, D)
        T = tables.shape[1]
        bs = pk.shape[1]
        k_scale = v_scale = None
        if sk is None:
            if pk.dtype == jnp.float32:
                # The exact plane: byte-for-byte the original path.
                pk = pk.at[wblock, wslot].set(kn)
                pv = pv.at[wblock, wslot].set(vn)
                kc = pk[tables].reshape(B, -1, H, D)
                vc = pv[tables].reshape(B, -1, H, D)
            else:
                # Scale-free cast storage (bf16).
                pk = pk.at[wblock, wslot].set(kn.astype(pk.dtype))
                pv = pv.at[wblock, wslot].set(vn.astype(pv.dtype))
                kc = pk[tables].astype(jnp.float32) \
                    .reshape(B, -1, H, D)
                vc = pv[tables].astype(jnp.float32) \
                    .reshape(B, -1, H, D)
        else:
            qmax = _KV_QMAX[kv_dtype]
            # 1. Grow the written blocks' scales (scatter-max; all
            #    pad writes land on the trash block, whose content
            #    is junk by contract).
            amax_k = jnp.max(jnp.abs(kn), axis=-1) / qmax  # (B,S_,H)
            amax_v = jnp.max(jnp.abs(vn), axis=-1) / qmax
            sk_new = sk.at[wblock].max(amax_k)
            sv_new = sv.at[wblock].max(amax_v)
            # 2. Rescale ONLY the written blocks' existing codes by
            #    old/new.  Duplicate wblock entries (chunk positions
            #    in one block) write identical rescaled rows, so the
            #    scatter collision is benign.
            rk = sk / jnp.where(sk_new > 0.0, sk_new, 1.0)
            rv = sv / jnp.where(sv_new > 0.0, sv_new, 1.0)
            old_k = pk[wblock].astype(jnp.float32) * \
                rk[wblock][:, :, None, :, None]
            old_v = pv[wblock].astype(jnp.float32) * \
                rv[wblock][:, :, None, :, None]
            if kv_dtype == "int8":
                old_k = jnp.round(old_k)
                old_v = jnp.round(old_v)
            pk = pk.at[wblock].set(old_k.astype(pk.dtype))
            pv = pv.at[wblock].set(old_v.astype(pv.dtype))
            # 3. Quantize the chunk's k/v at the grown scale and
            #    scatter the codes.
            pk = pk.at[wblock, wslot].set(_kv_quantize(
                kn, sk_new[wblock][..., None], kv_dtype))
            pv = pv.at[wblock, wslot].set(_kv_quantize(
                vn, sv_new[wblock][..., None], kv_dtype))
            sk, sv = sk_new, sv_new
            # 4. Gather codes + per-position scales; the dequant
            #    rides the attend kernel when it engages, else the
            #    dense fallback below.
            kc = pk[tables].reshape(B, -1, H, D)
            vc = pv[tables].reshape(B, -1, H, D)
            k_scale = jnp.broadcast_to(
                sk[tables][:, :, None, :],
                (B, T, bs, H)).reshape(B, -1, H)
            v_scale = jnp.broadcast_to(
                sv[tables][:, :, None, :],
                (B, T, bs, H)).reshape(B, -1, H)
        attn = attend(q, kc, vc, key_mask, k_scale=k_scale,
                      v_scale=v_scale) if attend is not None \
            else None
        if attn is None:
            if k_scale is not None:
                kc = kc.astype(jnp.float32) * k_scale[..., None]
                vc = vc.astype(jnp.float32) * v_scale[..., None]
            scores = jnp.einsum(
                "bqhd,bkhd->bqhk", q, kc,
                preferred_element_type=jnp.float32) / (D ** 0.5)
            scores = jnp.where(key_mask[:, :, None, :], scores,
                               -1e30)
            w = jax.nn.softmax(scores, axis=-1)
            attn = jnp.einsum("bqhk,bkhd->bqhd", w, vc)
        x = x + _mm(attn.reshape(B, S_, E), p, "wo") + p["bo"]
        h = ln(x, p["ln2_g"], p["ln2_b"])
        x = x + _mm(jnp.maximum(_mm(h, p, "w1") + p["b1"], 0.0),
                    p, "w2") + p["b2"]
        return x.astype(jnp.float32), pk, pv, sk, sv

    def _paged_lm_static(self):
        """Static geometry of the paged programs: (n_heads per block,
        positional-table size, vocab size).  The weight VALUES arrive
        per call through :meth:`_lm_params`."""
        emb, blocks, _head = self._lm_chain()
        n_heads = [int(e["config"]["n_heads"]) for e in blocks]
        P = int(self.weights[emb["params"]["pos"]].shape[0])
        V = int(self.weights[emb["params"]["weights"]].shape[0])
        return n_heads, P, V

    @staticmethod
    def _paged_storage_args(pool):
        """The storage leaves of a pool as jitted-program positional
        args, plus whether the pool is scaled-quantized — the shared
        unpack of every paged entry point."""
        ks, vs, sks, svs = _kv_unpack(pool.storage)
        if sks is None:
            return (ks, vs), False
        return (ks, vs, sks, svs), True

    def _build_paged_extend(self, Sc, T, block_size,
                            kv_dtype="f32"):
        """Jitted chunk prefill/extension against the block pool:
        each row's ``chunk_len`` real tokens (right-padded to the
        ``Sc`` bucket) are embedded at logical positions ``prior +
        i``, their k/v scattered into the row's table blocks, and
        the chunk attends the pool causally over absolute positions
        — ``prior = 0`` is a fresh prefill, ``prior = k·bs`` extends
        a shared prefix of k cached blocks, and a single-token chunk
        at ``prior = len-1`` re-derives the first logits of a fully
        prefix-cached prompt.  Returns the sampled first generated
        token per row (PRNG fold index 0, matching the bucketed
        path's stream)."""
        import jax
        import jax.numpy as jnp
        n_heads, P, V = self._paged_lm_static()
        bs = int(block_size)
        S_keys = T * bs

        def logits_of(params, x_last):
            return _head_logits(x_last, params["head_w"],
                                params["head_b"],
                                params.get("head_w__s"))

        sample_rows = _sample_rows
        att = self._decode_attend()
        quantized = _KV_QMAX[kv_dtype] is not None

        def paged_extend(params, pks, pvs, sks, svs, tables, tokens, prior,
                         chunk_len, temps, seeds):
            B = tables.shape[0]
            keys0 = jax.vmap(jax.random.PRNGKey)(seeds)
            offs = jnp.arange(Sc)
            # Logical positions (clipped: pad columns past the table
            # read junk that is never unmasked).
            posn = jnp.clip(prior[:, None] + offs[None, :], 0, P - 1)
            t = jnp.clip(tokens.astype(jnp.int32), 0, V - 1)
            x = params["emb_w"][t] + \
                jnp.take(params["emb_pos"], posn, axis=0)
            wpos = jnp.clip(prior[:, None] + offs[None, :], 0,
                            S_keys - 1)
            wblock = jnp.take_along_axis(tables, wpos // bs, axis=1)
            # Pad columns past each row's true chunk write to the
            # TRASH block explicitly: tables now cover exactly the
            # row's real span (lazy allocation), so the positional
            # clip above can land a junk column ON a real slot —
            # and a scatter collision with a real write is
            # update-order-undefined.
            wblock = jnp.where(offs[None, :] < chunk_len[:, None],
                               wblock, KVBlockPool.TRASH)
            wslot = wpos % bs
            qpos = prior[:, None] + offs[None, :]
            key_mask = (jnp.arange(S_keys)[None, None, :] <=
                        qpos[:, :, None])
            new_pks, new_pvs, new_sks, new_svs = [], [], [], []
            for i, (pk, pv, p, H) in enumerate(
                    zip(pks, pvs, params["blocks"], n_heads)):
                x, pk, pv, sk, sv = self._paged_block(
                    p, x, pk, pv, tables, wblock, wslot, key_mask, H,
                    attend=att, sk=sks[i] if quantized else None,
                    sv=svs[i] if quantized else None,
                    kv_dtype=kv_dtype)
                new_pks.append(pk)
                new_pvs.append(pv)
                new_sks.append(sk)
                new_svs.append(sv)
            idx = jnp.clip(chunk_len - 1, 0, Sc - 1)
            first_logits = logits_of(params, x[jnp.arange(B), idx])
            tok0 = sample_rows(
                first_logits,
                jax.vmap(lambda k: jax.random.fold_in(k, 0))(keys0),
                temps)
            return new_pks, new_pvs, new_sks, new_svs, tok0

        return jax.jit(paged_extend, donate_argnums=(1, 2, 3, 4))

    def _build_paged_step(self, T, block_size, kv_dtype="f32"):
        """Jitted one-token decode step over the block pool: each
        row feeds its previous token at position ``pos`` (k/v
        scattered to table block ``pos // bs``, slot ``pos % bs``),
        attends positions 0..pos through the gathered table, and
        samples the next token with PRNG fold index ``gen_idx`` —
        the same per-row stream as ``generate_bucketed``.  Rows of
        DIFFERENT requests, lengths, and ages share one call; pad
        rows carry all-trash tables and scatter junk into block 0."""
        import jax
        import jax.numpy as jnp
        n_heads, P, V = self._paged_lm_static()
        bs = int(block_size)
        S_keys = T * bs

        def logits_of(params, x_last):
            return _head_logits(x_last, params["head_w"],
                                params["head_b"],
                                params.get("head_w__s"))

        sample_rows = _sample_rows
        att = self._decode_attend()
        quantized = _KV_QMAX[kv_dtype] is not None

        def paged_step(params, pks, pvs, sks, svs, tables, pos, tok,
                       gen_idx, temps, seeds):
            keys0 = jax.vmap(jax.random.PRNGKey)(seeds)
            posn = jnp.clip(pos, 0, P - 1)
            x = params["emb_w"][jnp.clip(tok, 0, V - 1)][:, None] + \
                jnp.take(params["emb_pos"], posn, axis=0)[:, None]
            wpos = jnp.clip(pos, 0, S_keys - 1)
            wblock = jnp.take_along_axis(
                tables, (wpos // bs)[:, None], axis=1)
            wslot = (wpos % bs)[:, None]
            key_mask = (jnp.arange(S_keys)[None, None, :] <=
                        pos[:, None, None])
            new_pks, new_pvs, new_sks, new_svs = [], [], [], []
            for i, (pk, pv, p, H) in enumerate(
                    zip(pks, pvs, params["blocks"], n_heads)):
                x, pk, pv, sk, sv = self._paged_block(
                    p, x, pk, pv, tables, wblock, wslot, key_mask, H,
                    attend=att, sk=sks[i] if quantized else None,
                    sv=svs[i] if quantized else None,
                    kv_dtype=kv_dtype)
                new_pks.append(pk)
                new_pvs.append(pv)
                new_sks.append(sk)
                new_svs.append(sv)
            logits = logits_of(params, x[:, 0])
            tok_new = sample_rows(
                logits, jax.vmap(jax.random.fold_in)(keys0, gen_idx),
                temps)
            return new_pks, new_pvs, new_sks, new_svs, tok_new

        return jax.jit(paged_step, donate_argnums=(1, 2, 3, 4))

    def _build_paged_verify(self, K, T, block_size,
                            kv_dtype="f32"):
        """Jitted speculative-verify step over the block pool: each
        row feeds its current token PLUS ``K`` draft tokens as one
        ``K+1``-position chunk at positions ``pos..pos+K`` (k/v
        scattered through the table exactly like a prefill chunk),
        attends the pool under the per-position causal mask, and
        SAMPLES the target's token at EVERY chunk position — column
        ``j`` with PRNG fold index ``gen_idx + j``, the same per-row
        stream ``_build_paged_step`` would use at that generation
        index.  The caller compares column ``j``'s output against
        draft ``j+1`` host-side: the longest matching prefix is
        accepted and the first non-matching output is the bonus
        token, so greedy decode is BIT-IDENTICAL to the plain step
        loop (argmax over the same logits) and sampled decode draws
        the SAME stream the non-speculative path is the oracle for —
        for the deterministic drafters this is exactly the
        Leviathan accept/residual rule (accept draft x with
        probability p(x); on rejection the emitted token is p
        conditioned on != x).  Junk columns past a row's true draft
        count (``dlens``) scatter to the TRASH block — tables cover
        exactly the verify span under lazy allocation, so letting a
        clipped junk write land beside (or scatter-collide with) a
        real slot would corrupt the cache."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        n_heads, P, V = self._paged_lm_static()
        bs = int(block_size)
        S_keys = T * bs
        Sq = int(K) + 1

        def logits_of(params, x_last):
            return _head_logits(x_last, params["head_w"],
                                params["head_b"],
                                params.get("head_w__s"))

        sample_rows = _sample_rows
        att = self._decode_attend()
        quantized = _KV_QMAX[kv_dtype] is not None

        def paged_verify(params, pks, pvs, sks, svs, tables, pos, toks,
                         dlens, gen_idx, temps, seeds):
            keys0 = jax.vmap(jax.random.PRNGKey)(seeds)
            offs = jnp.arange(Sq)
            posn = jnp.clip(pos[:, None] + offs[None, :], 0, P - 1)
            x = params["emb_w"][jnp.clip(toks, 0, V - 1)] + \
                jnp.take(params["emb_pos"], posn, axis=0)
            wpos = jnp.clip(pos[:, None] + offs[None, :], 0,
                            S_keys - 1)
            wblock = jnp.take_along_axis(tables, wpos // bs, axis=1)
            # Column 0 is the row's current token, columns 1..dlen
            # its drafts; pad columns write to trash (see
            # _build_paged_extend — a clipped junk write colliding
            # with a real one is scatter-order-undefined).
            wblock = jnp.where(offs[None, :] <= dlens[:, None],
                               wblock, KVBlockPool.TRASH)
            wslot = wpos % bs
            qpos = pos[:, None] + offs[None, :]
            key_mask = (jnp.arange(S_keys)[None, None, :] <=
                        qpos[:, :, None])
            new_pks, new_pvs, new_sks, new_svs = [], [], [], []
            for i, (pk, pv, p, H) in enumerate(
                    zip(pks, pvs, params["blocks"], n_heads)):
                x, pk, pv, sk, sv = self._paged_block(
                    p, x, pk, pv, tables, wblock, wslot, key_mask, H,
                    attend=att, sk=sks[i] if quantized else None,
                    sv=svs[i] if quantized else None,
                    kv_dtype=kv_dtype)
                new_pks.append(pk)
                new_pvs.append(pv)
                new_sks.append(sk)
                new_svs.append(sv)
            logits = logits_of(params, x)  # (B, Sq, V)
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

            def drawn(_):
                # Per-column PRNG streams, exactly the plain step's
                # folds — only materialized when some row actually
                # samples (Sq categorical draws are a measurable
                # slice of the verify budget under greedy traffic,
                # and greedy IS argmax: _sample_rows would discard
                # the draw anyway).
                outs = []
                for j in range(Sq):
                    keys_j = jax.vmap(jax.random.fold_in)(
                        keys0, gen_idx + j)
                    outs.append(sample_rows(logits[:, j], keys_j,
                                            temps))
                return jnp.stack(outs, axis=1)

            out = lax.cond(jnp.any(temps > 0.0), drawn,
                           lambda _: greedy, None)
            return new_pks, new_pvs, new_sks, new_svs, out

        return jax.jit(paged_verify, donate_argnums=(1, 2, 3, 4))

    def paged_verify(self, pool, tables, pos, toks, draft_lens,
                     gen_idx, temps, seeds):
        """Speculative verify entry point for the serving engine:
        ``toks`` (B, K+1) holds each row's current token followed by
        up to K draft tokens (``draft_lens`` true counts); returns
        the (B, K+1) TARGET tokens (column j sampled with PRNG fold
        ``gen_idx + j``).  The caller accepts the longest prefix
        where draft j+1 equals output j and feeds the output at the
        first mismatch as the bonus token.  Compiles once per
        (B, K, T, n_blocks, block_size) — pool geometry and the
        decode-kernel mode ride the key like every paged program."""
        import jax
        tables = numpy.ascontiguousarray(tables, dtype=numpy.int32)
        toks = numpy.ascontiguousarray(toks, dtype=numpy.int32)
        B, T = tables.shape
        Sq = toks.shape[1]
        fn = self.compile_cache.get_or_build(
            ("pver", B, Sq, T, pool.n_blocks, pool.block_size,
             pool.kv_dtype, self._decode_weight_mode(),
             self._decode_kernel_mode()),
            lambda: self._build_paged_verify(Sq - 1, T,
                                             pool.block_size,
                                             pool.kv_dtype))
        store, quantized = self._paged_storage_args(pool)
        # Explicit upload — see paged_extend (strict_step contract).
        args = jax.device_put((
            tables,
            numpy.ascontiguousarray(pos, dtype=numpy.int32),
            toks,
            numpy.ascontiguousarray(draft_lens, dtype=numpy.int32),
            numpy.ascontiguousarray(gen_idx, dtype=numpy.int32),
            numpy.ascontiguousarray(temps, dtype=numpy.float32),
            numpy.ascontiguousarray(seeds, dtype=numpy.uint32)))
        ks, vs, sks, svs, out = fn(
            self._lm_params(), store[0], store[1],
            store[2] if quantized else None,
            store[3] if quantized else None, *args)
        pool.storage = (ks, vs, sks, svs) if quantized else (ks, vs)
        return numpy.asarray(out)

    def paged_extend(self, pool, tables, tokens, prior, chunk_lens,
                     temps, seeds):
        """Prefill/extend entry point for the serving engine:
        ``tables`` (B, T) int32 block tables (trash-padded),
        ``tokens`` (B, Sc) right-padded chunk tokens, ``prior`` (B,)
        cached positions per row, ``chunk_lens`` (B,) real chunk
        lengths.  Updates ``pool.storage`` in place (donated on
        accelerators) and returns the (B,) first generated tokens.
        Compiles once per (B, Sc, T, n_blocks, block_size) — POOL
        GEOMETRY IS PART OF THE KEY: resizing the pool or its blocks
        must never serve a stale program."""
        import jax
        tables = numpy.ascontiguousarray(tables, dtype=numpy.int32)
        tokens = numpy.ascontiguousarray(tokens, dtype=numpy.int32)
        B, T = tables.shape
        Sc = tokens.shape[1]
        fn = self.compile_cache.get_or_build(
            ("pext", B, Sc, T, pool.n_blocks, pool.block_size,
             pool.kv_dtype, self._decode_weight_mode(),
             self._decode_kernel_mode()),
            lambda: self._build_paged_extend(Sc, T, pool.block_size,
                                             pool.kv_dtype))
        store, quantized = self._paged_storage_args(pool)
        # EXPLICIT upload of the per-call host arrays: the serving
        # decode loop runs under analysis.runtime.strict_step, where
        # an implicit numpy→device transfer at dispatch raises.
        args = jax.device_put((
            tables, tokens,
            numpy.ascontiguousarray(prior, dtype=numpy.int32),
            numpy.ascontiguousarray(chunk_lens, dtype=numpy.int32),
            numpy.ascontiguousarray(temps, dtype=numpy.float32),
            numpy.ascontiguousarray(seeds, dtype=numpy.uint32)))
        ks, vs, sks, svs, tok0 = fn(
            self._lm_params(), store[0], store[1],
            store[2] if quantized else None,
            store[3] if quantized else None, *args)
        pool.storage = (ks, vs, sks, svs) if quantized else (ks, vs)
        return numpy.asarray(tok0)

    def paged_step(self, pool, tables, pos, tok, gen_idx, temps,
                   seeds):
        """One decode step for the engine's continuous batch: every
        active row advances one token through the pool.  Compiles
        once per (B, T, n_blocks, block_size)."""
        import jax
        tables = numpy.ascontiguousarray(tables, dtype=numpy.int32)
        B, T = tables.shape
        fn = self.compile_cache.get_or_build(
            ("pstep", B, T, pool.n_blocks, pool.block_size,
             pool.kv_dtype, self._decode_weight_mode(),
             self._decode_kernel_mode()),
            lambda: self._build_paged_step(T, pool.block_size,
                                           pool.kv_dtype))
        store, quantized = self._paged_storage_args(pool)
        # Explicit upload — see paged_extend (strict_step contract).
        args = jax.device_put((
            tables,
            numpy.ascontiguousarray(pos, dtype=numpy.int32),
            numpy.ascontiguousarray(tok, dtype=numpy.int32),
            numpy.ascontiguousarray(gen_idx, dtype=numpy.int32),
            numpy.ascontiguousarray(temps, dtype=numpy.float32),
            numpy.ascontiguousarray(seeds, dtype=numpy.uint32)))
        ks, vs, sks, svs, tok_new = fn(
            self._lm_params(), store[0], store[1],
            store[2] if quantized else None,
            store[3] if quantized else None, *args)
        pool.storage = (ks, vs, sks, svs) if quantized else (ks, vs)
        return numpy.asarray(tok_new)

    @staticmethod
    def _jax_pool(t, cfg, x):
        import jax.numpy as jnp
        from jax import lax
        ky, kx = int(cfg["ky"]), int(cfg["kx"])  # lint-ok: VL101 manifest int
        sh, sw = cfg["sliding"]
        (pt, pb), (pl, pr) = cfg["padding"]
        H, W = x.shape[1], x.shape[2]
        out_h = -(-(H + pt + pb - ky) // sh) + 1
        out_w = -(-(W + pl + pr - kx) // sw) + 1
        need_h = (out_h - 1) * sh + ky - (H + pt)
        need_w = (out_w - 1) * sw + kx - (W + pl)
        pad = ((0, 0), (pt, max(pb, need_h)),
               (pl, max(pr, need_w)), (0, 0))
        dims, strides = (1, ky, kx, 1), (1, sh, sw, 1)
        if t == "avg_pooling":
            ssum = lax.reduce_window(x, 0.0, lax.add, dims, strides,
                                     pad)
            cnt = lax.reduce_window(jnp.ones_like(x), 0.0, lax.add,
                                    dims, strides, pad)
            return ssum / cnt
        if t == "maxabs_pooling":
            hi = lax.reduce_window(x, -jnp.inf, lax.max, dims,
                                   strides, pad)
            lo = lax.reduce_window(x, jnp.inf, lax.min, dims,
                                   strides, pad)
            return jnp.where(-lo > hi, lo, hi)
        return lax.reduce_window(x, -jnp.inf, lax.max, dims, strides,
                                 pad)


def _np_softmax(v):
    e = numpy.exp(v - v.max(axis=-1, keepdims=True))
    return e / e.sum(axis=-1, keepdims=True)


#: Activation per dense-family unit type (shared by the numpy mirror
#: and the jax serving chain).
_DENSE_ACT = {
    "all2all": "linear", "all2all_tanh": "tanh",
    "all2all_relu": "softplus", "all2all_str": "str",
    "all2all_sigmoid": "sigmoid", "softmax": "softmax",
    "rbm": "sigmoid",
    "all2all_deconv": "linear",
    "all2all_deconv_sigmoid": "sigmoid",
    "all2all_deconv_tanh": "tanh",
}


_ACTS = {
    "linear": lambda v: v,
    "tanh": lambda v: TANH_A * numpy.tanh(TANH_B * v),
    "softplus": lambda v: numpy.log1p(numpy.exp(-numpy.abs(v))) +
    numpy.maximum(v, 0.0),
    "str": lambda v: numpy.maximum(v, 0.0),
    "sigmoid": lambda v: 1.0 / (1.0 + numpy.exp(-v)),
    "softmax": _np_softmax,
}


def _jax_act(name, v):
    import jax
    import jax.numpy as jnp
    return {
        "linear": lambda u: u,
        "tanh": lambda u: TANH_A * jnp.tanh(TANH_B * u),
        "softplus": jax.nn.softplus,
        "str": jax.nn.relu,
        "sigmoid": jax.nn.sigmoid,
        "softmax": lambda u: jax.nn.softmax(u, axis=-1),
    }[name](v)
