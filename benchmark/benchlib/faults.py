"""Faults planted under the timed path, for the controls and the
harness's own tests. Each breaks something a configuration states, in
the way a later PR might be tempted to ("hash less", "skip the
rebuild"); a run with one planted must report `correct: false`.

  encode-parity-altered   one byte of one parity row flipped where the
                          encode verb produces it (an answer altered)
  encode-digest-skipped   the control for the PUT cells: parity rows'
                          bitrot digests left zero — a PUT that hashes
                          12 rows of 16 is faster and breaks "bitrot
                          verified on every read"
  decode-output-altered   one byte of one rebuilt row flipped where the
                          decode verb produces it
  decode-skipped          the control for the GET cell: the rebuilt rows
                          left zero — the answer a degraded GET gives
                          when reconstruction is left out

Planted by wrapping the codec's two batch entries; the program's files
are not touched.
"""

from __future__ import annotations

import numpy as np

NAMES = ("encode-parity-altered", "encode-digest-skipped",
         "decode-output-altered", "decode-skipped")


def plant(name: str) -> None:
    from minio_tpu.object import codec as codec_mod
    if name not in NAMES:
        raise ValueError(f"unknown fault {name!r}; have {NAMES}")
    cls = codec_mod.Codec
    if name.startswith("encode"):
        orig = cls.encode_and_hash_batch

        def encode(self, data, algo, **kw):
            out = orig(self, data, algo, **kw)
            if out is None:
                return out
            full, digests = np.array(out[0]), np.array(out[1])
            if name == "encode-parity-altered":
                full[0, self.k, 0] ^= 1
            else:
                digests[:, self.k:, :] = 0
            return full, digests
        cls.encode_and_hash_batch = encode
    else:
        orig = cls.verify_and_decode_batch

        def decode(self, survivors, mask, shard_len, algo, **kw):
            out = orig(self, survivors, mask, shard_len, algo, **kw)
            if out is None:
                return out
            missing = np.array(out[0])
            if name == "decode-output-altered":
                missing[0, 0, 0] ^= 1
            else:
                missing[...] = 0
            return missing, out[1], out[2]
        cls.verify_and_decode_batch = decode
