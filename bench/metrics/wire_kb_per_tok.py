"""Bytes the compiled decode step's collectives put on the chip-to-chip wire, over the mesh, per token (one per slot)."""


def read(rec):
    if not rec.wire:
        return None
    total = sum(rec.wire.get("decode", {}).values())
    return total / rec.slots / 1000.0 if total else None
