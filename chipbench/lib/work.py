"""Work a commit needs, from shapes alone, whatever tier implements it."""
from __future__ import annotations

# one message: int32 target + 4-byte value (int32 distance or float32
# contribution) + one validity byte
MESSAGE_BYTES = 4 + 4 + 1
# the state is read once and written once, 4 bytes per vertex each way
STATE_BYTES_PER_VERTEX = 2 * 4


def commit_bytes(messages: int, vertices: int) -> int:
    """HBM bytes one commit of ``messages`` messages into a
    ``vertices``-long state has to move."""
    return messages * MESSAGE_BYTES + vertices * STATE_BYTES_PER_VERTEX
