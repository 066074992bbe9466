"""Zero-trust request envelopes (paper §3.4.6).

Every API request is a signed envelope::

    {"payloadtype": "submitfunctionspec", "payload": "<json>", "signature": "<hex>"}

The server recovers the signer identity from (payloadtype || payload,
signature) — *never trust, always verify* — and authorizes against the
three-role model: server owner, colony owner, executor/user member.
"""

from __future__ import annotations

import json
import time
from typing import Any

from . import tracing
from .crypto import Crypto
from .errors import AuthError


def canonical(payload: dict) -> str:
    return json.dumps(payload, sort_keys=True, separators=(",", ":"))


def sign_envelope(
    payloadtype: str, payload: dict, prvkey: str, msgid: str | None = None
) -> dict:
    """Sign an envelope; an idempotency key (``msgid``) is folded into the
    signed string so a replayed-by-attacker envelope cannot be re-keyed
    (tampering with msgid breaks signature recovery — see ROBUSTNESS.md).
    Envelopes without a msgid sign exactly as before (back-compat)."""
    body = canonical(payload)
    with tracing.span("colony.rpc.sign", payloadtype=payloadtype) as sp:
        t = time.thread_time()
        sig = Crypto.sign(payloadtype + body + (msgid or ""), prvkey)
        cpu_s = time.thread_time() - t
        sp.set(cpu_s=cpu_s)
    tracing.count(f"rpc.sign.{payloadtype}", seconds=cpu_s)
    env = {"payloadtype": payloadtype, "payload": body, "signature": sig}
    if msgid:
        env["msgid"] = msgid
    return env


def open_envelope(
    env: dict, verify: bool = True, allow_unverified: bool = False
) -> tuple[str, str, dict[str, Any]]:
    """Returns (identity, payloadtype, payload). Raises AuthError on tamper.

    ``verify=False`` trusts the envelope's bare ``identity`` claim and is
    legitimate only for in-process benchmark/test harnesses: the caller
    must opt in with ``allow_unverified=True`` so a transport can never
    reach the unverified path by accident (network transports always
    verify — see ``ColoniesServer.handle(external=True)``).
    """
    ptype = env.get("payloadtype", "")
    body = env.get("payload", "")
    if isinstance(body, dict):  # allow pre-parsed payloads on the in-proc path
        body = canonical(body)
    payload = json.loads(body) if body else {}
    if not verify:
        if not allow_unverified:
            raise AuthError(
                "open_envelope(verify=False) requires allow_unverified=True"
                " (in-process harnesses only; never trust, always verify)"
            )
        return env.get("identity", "unverified"), ptype, payload
    sig = env.get("signature", "")
    if not sig:
        raise AuthError("missing signature")
    with tracing.span("colony.rpc.verify", payloadtype=ptype) as sp:
        t = time.thread_time()
        try:
            identity = Crypto.recover(ptype + body + env.get("msgid", ""), sig)
        except (ValueError, AssertionError) as e:
            raise AuthError(f"signature recovery failed: {e}") from e
        finally:
            cpu_s = time.thread_time() - t
            sp.set(cpu_s=cpu_s)
            tracing.count(f"rpc.verify.{ptype}", seconds=cpu_s)
    return identity, ptype, payload
