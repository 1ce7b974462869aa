"""SignatureSet — the pure-data interchange record for batch verification.

Matches GenericSignatureSet
(/root/reference/crypto/bls/src/generic_signature_set.rs:61): one (aggregate)
signature, one or more signing public keys, and a single 32-byte message.
Sets are what the chain layers accumulate and hand to the crypto backend —
on TPU, batches of these are what the vmapped pairing kernel consumes.

A set built from registry indices may carry them (`signing_indices`, one a
key, in the keys' order) and the key table they are rows of
(`signing_registry`: the builder's pubkey cache's, an opaque object read by
identity): a backend that keeps THAT registry's keys on the device
(crypto/jaxbls/registry.py) then gathers the keys by index instead of
packing them; every other backend, and that backend for any other
registry's set, reads `signing_keys` alone.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Sequence

import numpy as np

from .keys import PublicKey
from .signature import Signature


@dataclass(frozen=True)
class SignatureSet:
    signature: Signature
    signing_keys: tuple[PublicKey, ...]
    message: bytes  # 32-byte signing root
    # validator index of each signing key (int64, read-only), or None
    signing_indices: np.ndarray | None = field(default=None, compare=False)
    # the key table those indices are rows of, or None
    signing_registry: object = field(default=None, compare=False)

    def __init__(self, signature: Signature, signing_keys: Sequence[PublicKey],
                 message: bytes, signing_indices: Sequence[int] | None = None,
                 signing_registry: object = None):
        if len(message) != 32:
            raise ValueError("SignatureSet message must be a 32-byte root")
        if len(signing_keys) == 0:
            raise ValueError("SignatureSet requires at least one signing key")
        object.__setattr__(self, "signature", signature)
        object.__setattr__(self, "signing_keys", tuple(signing_keys))
        object.__setattr__(self, "message", bytes(message))
        if signing_indices is not None:
            signing_indices = np.array(signing_indices, dtype=np.int64)
            if signing_indices.shape != (len(self.signing_keys),):
                raise ValueError("SignatureSet needs one signing index a key")
            signing_indices.setflags(write=False)
        elif signing_registry is not None:
            raise ValueError("a registry without indices names no row")
        object.__setattr__(self, "signing_indices", signing_indices)
        object.__setattr__(self, "signing_registry", signing_registry)

    @classmethod
    def single_pubkey(cls, signature: Signature, signing_key: PublicKey, message: bytes):
        return cls(signature, (signing_key,), message)

    @classmethod
    def multiple_pubkeys(cls, signature: Signature, signing_keys: Sequence[PublicKey], message: bytes):
        return cls(signature, signing_keys, message)
