"""Loopback HTTP client: sockets and bytes only, one keep-alive connection
per thread (copied in idea from chip_smoke.Client)."""

from __future__ import annotations

import http.client
import threading
from typing import Dict, Optional, Tuple


class Client:
    def __init__(self, port: int, headers: Optional[Dict[str, str]] = None,
                 timeout_s: float = 120.0):
        self._port = port
        self._timeout = timeout_s
        self._headers = {"Content-Type": "application/json"}
        self._headers.update(headers or {})
        self._tls = threading.local()

    def request(self, method: str, path: str,
                body: Optional[bytes] = None) -> Tuple[int, bytes]:
        conn = getattr(self._tls, "conn", None)
        if conn is None:
            conn = http.client.HTTPConnection("127.0.0.1", self._port,
                                              timeout=self._timeout)
            self._tls.conn = conn
        try:
            conn.request(method, path, body=body, headers=self._headers)
            resp = conn.getresponse()
            return resp.status, resp.read()
        except (OSError, http.client.HTTPException):
            conn.close()
            self._tls.conn = None
            raise

    def post(self, path: str, body: bytes) -> Tuple[int, bytes]:
        return self.request("POST", path, body)

    def get(self, path: str) -> Tuple[int, bytes]:
        return self.request("GET", path)
