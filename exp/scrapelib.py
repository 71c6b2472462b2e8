"""Shared plumbing for the CI scrape gates (scrape_metrics /
scrape_traces): the raw HTTP/1.1 fetch against the
broker's stats listener. One implementation so a fetch-path fix (the
read-to-EOF rule, timeouts) lands in every gate at once."""

import asyncio


async def http_get(addr: str, path: str, timeout: float = 5.0):
    """``(status_head, body)`` for one GET against ``host:port``. The
    listener sends ``Connection: close``, so the body is read to EOF —
    a large exposition split across TCP segments never truncates."""
    host, port = addr.rsplit(":", 1)
    reader, writer = await asyncio.open_connection(host, int(port))
    writer.write(f"GET {path} HTTP/1.1\r\nHost: x\r\n\r\n".encode())
    await writer.drain()
    raw = b""
    while True:
        chunk = await asyncio.wait_for(reader.read(65536), timeout)
        if not chunk:
            break
        raw += chunk
    writer.close()
    head, body = raw.split(b"\r\n\r\n", 1)
    return head, body
