"""AWS Signature Version 4, header form — the benchmark's own signer.

Written from the public specification ("Signature Version 4 signing
process", AWS General Reference); shares no code with the server's
verifier. The payload hash is the real SHA-256 of the body (signed
payload), computed by the caller before the measured window.
"""

from __future__ import annotations

import datetime
import hashlib
import hmac
import urllib.parse

REGION = "us-east-1"
ALGORITHM = "AWS4-HMAC-SHA256"


def _hmac(key: bytes, msg: str) -> bytes:
    return hmac.new(key, msg.encode(), hashlib.sha256).digest()


def signing_key(secret: str, date: str, region: str = REGION) -> bytes:
    k = _hmac(("AWS4" + secret).encode(), date)
    return _hmac(_hmac(_hmac(k, region), "s3"), "aws4_request")


def sign(method: str, path: str, query: dict[str, str], host: str,
         payload_sha256: str, access_key: str, secret_key: str,
         region: str = REGION) -> dict[str, str]:
    """Headers (host, x-amz-date, x-amz-content-sha256, authorization)
    for one request; `path` is the unencoded absolute path."""
    now = datetime.datetime.now(datetime.timezone.utc)
    amz_date = now.strftime("%Y%m%dT%H%M%SZ")
    date = now.strftime("%Y%m%d")
    headers = {"host": host, "x-amz-content-sha256": payload_sha256,
               "x-amz-date": amz_date}
    signed = ";".join(sorted(headers))
    canonical_query = "&".join(
        f"{urllib.parse.quote(k, safe='-_.~')}="
        f"{urllib.parse.quote(v, safe='-_.~')}"
        for k, v in sorted(query.items()))
    canonical = "\n".join([
        method, urllib.parse.quote(path, safe="/-_.~"), canonical_query,
        "".join(f"{h}:{headers[h]}\n" for h in sorted(headers)),
        signed, payload_sha256])
    scope = f"{date}/{region}/s3/aws4_request"
    to_sign = "\n".join([ALGORITHM, amz_date, scope,
                         hashlib.sha256(canonical.encode()).hexdigest()])
    sig = hmac.new(signing_key(secret_key, date, region), to_sign.encode(),
                   hashlib.sha256).hexdigest()
    headers["authorization"] = (
        f"{ALGORITHM} Credential={access_key}/{scope}, "
        f"SignedHeaders={signed}, Signature={sig}")
    return headers
