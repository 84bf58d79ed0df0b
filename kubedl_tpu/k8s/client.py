"""Minimal Kubernetes REST client (stdlib-only: http.client + ssl).

Speaks the apiserver wire protocol the reference consumes through
client-go (ref main.go:70-75, pkg/util/k8sutil/k8sutil.go:37-70 cluster
config resolution): JSON CRUD with optimistic concurrency via
metadata.resourceVersion, label-selector lists, and chunked watch streams
(one JSON event per line). Config resolution order mirrors the reference:
explicit args > in-cluster service account > $KUBECONFIG (token/CA subset).
"""
from __future__ import annotations

import http.client
import json
import os
import ssl
import threading
import urllib.parse
from typing import Any, Dict, Iterator, Optional, Tuple

SA_DIR = "/var/run/secrets/kubernetes.io/serviceaccount"


class KubeApiError(Exception):
    def __init__(self, status: int, message: str = "") -> None:
        super().__init__(f"HTTP {status}: {message}")
        self.status = status
        self.message = message


class KubeClient:
    def __init__(
        self,
        base_url: str,
        token: Optional[str] = None,
        ca_file: Optional[str] = None,
        insecure_skip_verify: bool = False,
        timeout: float = 30.0,
    ) -> None:
        parsed = urllib.parse.urlparse(base_url)
        self.scheme = parsed.scheme or "http"
        self.host = parsed.hostname or "localhost"
        self.port = parsed.port or (443 if self.scheme == "https" else 80)
        self.token = token
        self.timeout = timeout
        self._local = threading.local()
        if self.scheme == "https":
            if insecure_skip_verify:
                self._ssl = ssl._create_unverified_context()
            else:
                self._ssl = ssl.create_default_context(cafile=ca_file)
        else:
            self._ssl = None

    # -- config resolution (ref k8sutil.go:37-70) -------------------------

    @staticmethod
    def in_cluster() -> "KubeClient":
        host = os.environ["KUBERNETES_SERVICE_HOST"]
        port = os.environ.get("KUBERNETES_SERVICE_PORT", "443")
        with open(os.path.join(SA_DIR, "token")) as f:
            token = f.read().strip()
        return KubeClient(
            f"https://{host}:{port}", token=token,
            ca_file=os.path.join(SA_DIR, "ca.crt"),
        )

    @staticmethod
    def from_kubeconfig(path: Optional[str] = None) -> "KubeClient":
        """Token/CA subset of kubeconfig (enough for GKE token auth)."""
        import yaml

        path = path or os.environ.get("KUBECONFIG", os.path.expanduser("~/.kube/config"))
        with open(path) as f:
            cfg = yaml.safe_load(f)
        ctx_name = cfg.get("current-context", "")
        ctx = next(c["context"] for c in cfg.get("contexts", []) if c["name"] == ctx_name)
        cluster = next(
            c["cluster"] for c in cfg.get("clusters", []) if c["name"] == ctx["cluster"]
        )
        user = next(u["user"] for u in cfg.get("users", []) if u["name"] == ctx["user"])
        return KubeClient(
            cluster["server"],
            token=user.get("token"),
            ca_file=cluster.get("certificate-authority"),
            insecure_skip_verify=bool(cluster.get("insecure-skip-tls-verify")),
        )

    @staticmethod
    def resolve(base_url: Optional[str] = None) -> "KubeClient":
        if base_url:
            return KubeClient(base_url)
        if "KUBERNETES_SERVICE_HOST" in os.environ and os.path.exists(SA_DIR):
            return KubeClient.in_cluster()
        return KubeClient.from_kubeconfig()

    # -- transport --------------------------------------------------------

    def _conn(self) -> http.client.HTTPConnection:
        conn = getattr(self._local, "conn", None)
        if conn is None:
            conn = self._new_conn(self.timeout)
            self._local.conn = conn
        return conn

    def _new_conn(self, timeout: Optional[float]) -> http.client.HTTPConnection:
        if self.scheme == "https":
            return http.client.HTTPSConnection(
                self.host, self.port, timeout=timeout, context=self._ssl
            )
        return http.client.HTTPConnection(self.host, self.port, timeout=timeout)

    def _headers(self) -> Dict[str, str]:
        h = {"Accept": "application/json", "Content-Type": "application/json"}
        if self.token:
            h["Authorization"] = f"Bearer {self.token}"
        return h

    def request(
        self,
        method: str,
        path: str,
        body: Optional[Any] = None,
        params: Optional[Dict[str, str]] = None,
    ) -> Any:
        if params:
            path = f"{path}?{urllib.parse.urlencode(params)}"
        payload = json.dumps(body) if body is not None else None
        for attempt in (0, 1):  # one retry on a stale keep-alive connection
            conn = self._conn()
            try:
                conn.request(method, path, body=payload, headers=self._headers())
                resp = conn.getresponse()
                data = resp.read()
                break
            except (http.client.HTTPException, ConnectionError, OSError):
                self._local.conn = None
                if attempt:
                    raise
        if resp.status >= 400:
            msg = ""
            try:
                msg = json.loads(data).get("message", "")
            except (json.JSONDecodeError, AttributeError):
                msg = data.decode(errors="replace")[:200]
            raise KubeApiError(resp.status, msg)
        return json.loads(data) if data else None

    def watch(
        self,
        path: str,
        params: Optional[Dict[str, str]] = None,
        conn_holder: Optional[list] = None,
        abort=None,
    ) -> Iterator[Tuple[str, Dict]]:
        """Stream watch events until the server closes the connection.

        Uses a dedicated connection with no read timeout; the caller owns
        reconnect-with-last-resourceVersion (store.py does). If given,
        `conn_holder` receives the live connection so a stopper can close
        it from another thread and unblock the chunked read. `abort` is
        re-checked AFTER the connection is registered: a stopper either
        ran before registration (abort() is True -> return) or after (the
        registered conn gets shut down) — no unstoppable window. The
        socket is opened BEFORE registration for the same reason: a
        stopper shuts down `conn.sock`, and http.client opens it only at
        the first request."""
        params = dict(params or {})
        params["watch"] = "true"
        qs = urllib.parse.urlencode(params)
        conn = self._new_conn(None)
        conn.connect()
        if conn_holder is not None:
            conn_holder.append(conn)
        if abort is not None and abort():
            if conn_holder is not None:
                conn_holder.remove(conn)
            conn.close()
            return
        try:
            conn.request("GET", f"{path}?{qs}", headers=self._headers())
            resp = conn.getresponse()
            if resp.status >= 400:
                raise KubeApiError(resp.status, resp.read().decode(errors="replace")[:200])
            buf = b""
            while True:
                chunk = resp.read1(65536)
                if not chunk:
                    return
                buf += chunk
                while b"\n" in buf:
                    line, buf = buf.split(b"\n", 1)
                    if not line.strip():
                        continue
                    ev = json.loads(line)
                    yield ev.get("type", ""), ev.get("object", {})
        finally:
            if conn_holder is not None and conn in conn_holder:
                conn_holder.remove(conn)
            conn.close()
