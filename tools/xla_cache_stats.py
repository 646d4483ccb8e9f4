"""Mine the persistent XLA compilation cache for offline perf evidence.

The cache directory (``ddw_tpu.utils.compile_cache``: what
``JAX_COMPILATION_CACHE_DIR`` names, else ``.jax_cache`` in the checkout)
holds compiled executables from every cached compile — including TPU modules
a chip run brought back. Each entry is
``zstd(4-byte big-endian compile-seconds + backend.serialize_executable())``
(jax ``compilation_cache.combine_executable_and_time``). This tool lets gap
analysis proceed with no device:

- **always** (no backend needed): entry name, size, recorded compile time;
- **when this process's backend matches the entry's platform**: deserializes
  and dumps optimized-HLO statistics — instruction mix by opcode, fusion /
  collective / dot / custom-call counts — the "what did XLA actually emit"
  table behind the MFU-gap analysis;
- entries for OTHER platforms (e.g. TPU entries read on a CPU host) fall
  back to a raw metadata scan of the serialized module: op_name counts are
  approximate but extractable without the device.

Usage: ``python tools/xla_cache_stats.py [cache_dir] [--match SUBSTR]
[--top N] [--hlo-out DIR]``; ``--hlo-out`` writes each deserialized module's
full optimized HLO text for manual reading. Prints ONE JSON line; the
human-readable table goes to stderr.
"""

import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))
# entries compiled on a different microarch make the CPU AOT loader spew
# feature-mismatch error walls on every deserialize; they are harmless here
# (we only read the HLO, never execute)
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

import argparse
import collections
import glob
import json
import re

from ddw_tpu.utils.compile_cache import compile_cache_dir


# instruction lines in optimized HLO text: "  %name = type opcode(...)" or
# "  name.N = type opcode(...)"; opcode is the token before '('
_INSTR_RE = re.compile(r"^\s+(?:ROOT\s+)?%?[\w.\-]+\s*=\s*\S+\s+([\w\-]+)\(",
                       re.M)

_FAMILIES = (
    ("dot", ("dot", "dot-general")),
    ("conv", ("convolution",)),
    ("fusion", ("fusion",)),
    ("collective", ("all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute", "collective-broadcast",
                    "all-reduce-start", "all-gather-start")),
    ("custom-call", ("custom-call",)),
    ("copy", ("copy", "copy-start", "transpose", "bitcast")),
)


def family_of(opcode: str) -> str:
    for fam, ops in _FAMILIES:
        if opcode in ops:
            return fam
    return "other"


def decompress(path: str) -> tuple[int, bytes]:
    """-> (compile_seconds, serialized_executable).

    jax writes zstd entries when ``zstandard`` is importable and falls back
    to zlib otherwise (``jax/_src/compilation_cache.py``) — mirror that by
    sniffing the zstd magic so entries from either kind of host mine."""
    raw = open(path, "rb").read()
    if raw[:4] == b"\x28\xb5\x2f\xfd":
        import zstandard

        blob = zstandard.ZstdDecompressor().decompress(
            raw, max_output_size=1 << 31)
    else:
        import zlib

        blob = zlib.decompress(raw)
    return int.from_bytes(blob[:4], "big"), blob[4:]


def hlo_stats(hlo_text: str) -> dict:
    ops = collections.Counter(_INSTR_RE.findall(hlo_text))
    fams = collections.Counter()
    for op, n in ops.items():
        fams[family_of(op)] += n
    return {"n_instructions": sum(ops.values()),
            "families": dict(fams.most_common()),
            "top_opcodes": dict(ops.most_common(12))}


def raw_scan(serialized: bytes) -> dict:
    """Backend-free approximation: count op_name metadata strings inside the
    serialized module proto (readable even for foreign-platform entries)."""
    # longer alternative first: bare jvp( would otherwise always win and
    # the transpose(...)-tagged backward ops would never be counted
    names = re.findall(rb"transpose\(jvp\([\w]+\)\)|jvp\([\w]+\)", serialized)
    kinds = collections.Counter()
    for pat, label in ((rb"\bfusion\.\d+", "fusion"),
                       (rb"\bdot\.\d+|\bdot_general", "dot"),
                       (rb"\bconvolution\.?\d*", "conv"),
                       (rb"all-reduce|all-gather|reduce-scatter", "collective"),
                       (rb"custom-call", "custom-call")):
        kinds[label] = len(re.findall(pat, serialized))
    return {"metadata_hits": len(names), "approx_counts": dict(kinds)}


def try_deserialize(serialized: bytes):
    """Optimized HLO text via the current backend, or None if it can't load
    this entry (foreign platform / incompatible build)."""
    try:
        import jax
        from jaxlib import _jax

        client = jax.devices()[0].client
        ex = client.deserialize_executable(
            serialized, _jax.DeviceList(tuple(jax.devices())))
        return "\n".join(m.to_string() for m in ex.hlo_modules())
    except Exception:
        return None


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("cache_dir", nargs="?", default=compile_cache_dir())
    ap.add_argument("--match", default="", help="only entries whose filename "
                    "contains this substring")
    ap.add_argument("--top", type=int, default=0,
                    help="only the N largest entries (0 = all)")
    ap.add_argument("--hlo-out", default="",
                    help="write each deserialized module's HLO text here")
    args = ap.parse_args()

    paths = sorted(glob.glob(os.path.join(args.cache_dir, "*-cache")),
                   key=os.path.getsize, reverse=True)
    paths = [p for p in paths if args.match in os.path.basename(p)]
    if args.top:
        paths = paths[:args.top]
    if not paths:
        raise SystemExit(f"no cache entries under {args.cache_dir}"
                         + (f" matching {args.match!r}" if args.match else ""))
    if args.hlo_out:
        os.makedirs(args.hlo_out, exist_ok=True)

    out = {"cache_dir": args.cache_dir, "entries": []}
    for p in paths:
        base = os.path.basename(p)
        name = base.rsplit("-", 2)[0]
        row = {"name": name, "file": base,
               "bytes": os.path.getsize(p)}
        try:
            compile_s, ser = decompress(p)
        except Exception as e:
            row["error"] = f"decompress: {e}"
            out["entries"].append(row)
            continue
        row["compile_s"] = compile_s
        hlo = try_deserialize(ser)
        if hlo is not None:
            row["method"] = "hlo"
            row.update(hlo_stats(hlo))
            if args.hlo_out:
                fp = os.path.join(args.hlo_out, base + ".hlo.txt")
                with open(fp, "w") as f:
                    f.write(hlo)
                row["hlo_path"] = fp
        else:
            row["method"] = "raw-scan"
            row.update(raw_scan(ser))
        out["entries"].append(row)
        fams = row.get("families") or row.get("approx_counts") or {}
        print(f"[{row['method']:<8}] {name[:36]:<36} {row['bytes']:>9}B "
              f"compile={compile_s:>4}s "
              + " ".join(f"{k}={v}" for k, v in list(fams.items())[:5]),
              file=sys.stderr, flush=True)

    total_compile = sum(r.get("compile_s", 0) for r in out["entries"])
    out["total_compile_s"] = total_compile
    print(f"[total] {len(out['entries'])} entries, {total_compile}s of "
          f"recorded compile time banked", file=sys.stderr)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
