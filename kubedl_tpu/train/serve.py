"""HTTP serving workload — the continuous-batching engine as a JAXJob.

Completes the operator's train -> checkpoint -> serve loop: a JAXJob
runs this module (examples/jax_job_serving.yaml), it restores params
from the trainer's Orbax checkpoint, and serves generation over a small
JSON API backed by `models/serving.ServingEngine`:

    POST /generate   {"tokens": [..], "max_new_tokens": 64,
                      "eos_token": 2?, "prefix_id": 0?} -> {"tokens": [...]}
                     (with an --hf-model tokenizer, {"text": "..."} works
                      too and the response adds decoded "text")
    POST /generate   {"requests": [{...}, ...]}  (batch form; each entry
                      rides its own engine slot)  -> {"results": [...]}
    POST /prefix     {"tokens": [...]}  -> {"prefix_id": N}   (shared
                      system prompts prefill once; see register_prefix)
    GET  /stats      -> ServingEngine.stats()
    GET  /metrics    -> Prometheus text format (kubedl_serving_* gauges)
    GET  /healthz    -> {"ok": true}

One background thread drives `engine.step()` whenever work is pending —
request handlers only enqueue and wait, so concurrent HTTP clients
batch onto the same decode ticks (that's the continuous-batching win).
The reference has no serving stack at all (SURVEY §2.4).
"""
from __future__ import annotations

import argparse
import json
import logging
import os
import sys
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

log = logging.getLogger("kubedl_tpu.serve")


def parse_args(argv=None):
    p = argparse.ArgumentParser("kubedl-serve")
    p.add_argument("--model", default=os.environ.get("KUBEDL_MODEL", "tiny"),
                   choices=["tiny", "bench-150m", "bench-1b", "llama-7b"])
    p.add_argument("--checkpoint-path",
                   default=os.environ.get("KUBEDL_CHECKPOINT_PATH", ""))
    p.add_argument("--hf-model", default=os.environ.get("KUBEDL_HF_MODEL", ""),
                   help="Hugging Face Llama name/dir — overrides --model/"
                        "--checkpoint-path (models/import_hf.py)")
    p.add_argument("--allow-fresh-init", action="store_true")
    p.add_argument("--lora-checkpoint-path", default="",
                   help="merge the newest adapter checkpoint from a trainer "
                        "--lora-rank run into the base weights")
    p.add_argument("--lora-alpha", type=float, default=None)
    p.add_argument("--adapter", action="append", default=[],
                   metavar="CKPT[:ALPHA]",
                   help="register a LoRA adapter checkpoint at startup "
                        "for per-request selection (repeatable; ids are "
                        "assigned in order starting at 1). Unlike "
                        "--lora-checkpoint-path (which MERGES one adapter "
                        "into the weights), these serve side-by-side with "
                        "the base model")
    p.add_argument("--bind", default="0.0.0.0")
    p.add_argument("--port", type=int, default=int(os.environ.get("PORT", 8000)))
    # operator pods get these via the spec.serving KUBEDL_SERVING_*
    # injection (workloads/jaxjob.py); flags still win when passed
    p.add_argument("--slots", type=int,
                   default=int(os.environ.get("KUBEDL_SERVING_SLOTS", 8)))
    p.add_argument("--max-len", type=int,
                   default=int(os.environ.get("KUBEDL_SERVING_MAX_LEN", 1024)))
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--int8", action="store_true",
                   help="weight-only int8 (models/quant.py)")
    p.add_argument("--kv-int8", action="store_true",
                   help="int8 KV cache with exact scale folding — half the "
                        "per-token cache read at long contexts")
    p.add_argument("--draft-model", default="",
                   help="named config for a speculative draft model "
                        "(models/llama.py config_for); requires "
                        "--draft-checkpoint-path or --draft-hf-model")
    p.add_argument("--draft-checkpoint-path", default="",
                   help="Orbax checkpoint for the draft model")
    p.add_argument("--draft-hf-model", default="",
                   help="HF checkpoint for the draft model (must share "
                        "the target's tokenizer)")
    p.add_argument("--spec-k", type=int, default=4,
                   help="draft tokens proposed per speculative round")
    p.add_argument("--max-steps", type=int, default=0,
                   help="stop after N pump passes, each up to --decode-block "
                        "device ticks (smoke tests); 0 = forever")
    p.add_argument("--decode-block", type=int, default=8,
                   help="max ticks fused per host sync (serving.py "
                        "step_block): bigger amortizes dispatch/sync "
                        "overhead, smaller tightens streaming latency; "
                        "1 = tick per sync")
    return p.parse_args(argv)


class _Service:
    """Engine + queue pump shared by all HTTP handler threads."""

    def __init__(self, engine, tokenizer=None, decode_block: int = 8) -> None:
        self.engine = engine
        self.tokenizer = tokenizer
        self.decode_block = max(int(decode_block), 1)
        self._lock = threading.Lock()  # engine calls are single-threaded
        self._work = threading.Event()
        self._stop = threading.Event()
        self.ticks = 0
        self._thread = threading.Thread(
            target=self._pump, name="serve-pump", daemon=True)
        self._thread.start()

    def _pump(self) -> None:
        while not self._stop.is_set():
            if not self._work.wait(timeout=0.1):
                continue
            with self._lock:
                if not self.engine.has_pending():
                    self._work.clear()
                    continue
                try:
                    if self.decode_block > 1:
                        self.engine.step_block(self.decode_block)
                    else:
                        self.engine.step()
                except Exception as e:  # noqa: BLE001
                    # a step that throws (bad state, OOM, device error)
                    # must not kill the pump thread silently: waiting
                    # clients would hang until their timeouts while
                    # submits keep returning 200. Fail the in-flight
                    # work loudly and keep serving.
                    print(f"serve pump: engine step failed: "
                          f"{type(e).__name__}: {e}", flush=True)
                    for req in list(self.engine._queue) + [
                            r for r in self.engine._slot_req
                            if r is not None]:
                        self.engine.cancel(req)
                # pump passes, not device ticks: the smoke-mode budget
                # just needs a monotonic progress counter
                self.ticks += 1

    def submit(self, prompt, max_new_tokens: int, eos_token: Optional[int],
               prefix_id: Optional[int] = None,
               temperature: Optional[float] = None,
               top_k: int = 0, top_p: float = 1.0,
               logprobs: bool = False, adapter_id: int = 0, stop=None):
        with self._lock:
            req = self.engine.submit(prompt, max_new_tokens, eos_token,
                                     prefix_id=prefix_id,
                                     temperature=temperature,
                                     top_k=top_k, top_p=top_p,
                                     logprobs=logprobs,
                                     adapter_id=adapter_id, stop=stop)
        self._work.set()
        return req

    def register_adapter(self, checkpoint_path: str, alpha=None) -> int:
        """Load a trainer --lora-rank adapter checkpoint and register it
        for per-request selection. The disk restore runs OUTSIDE the
        service lock (it can take seconds); only the registry swap —
        which retraces the next tick — holds it."""
        from kubedl_tpu.train.generate import restore_params

        adapters = restore_params(checkpoint_path, label="lora adapters")
        if adapters is None:
            raise ValueError(
                f"no adapter checkpoint under {checkpoint_path!r}")
        with self._lock:
            return self.engine.register_adapter(adapters, alpha=alpha)

    def register_prefix(self, tokens) -> int:
        # NOT under the service lock: the prefill compile can take tens
        # of seconds on a real chip and must not freeze the tick pump;
        # the engine's own prefix lock guards its registry
        return self.engine.register_prefix(tokens)

    def kick(self) -> None:
        """Nudge the pump (streaming handlers poll instead of wait())."""
        self._work.set()

    def wait(self, reqs, timeout: float = 300.0) -> bool:
        import time

        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if all(r.done for r in reqs):
                return True
            self._work.set()
            time.sleep(0.005)
        return False

    def cancel(self, reqs) -> None:
        with self._lock:
            for r in reqs:
                self.engine.cancel(r)

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=5)


def _parse_stop(value, tok):
    """"stop" field -> list of token-id sequences. Accepts one string, a
    list of strings (tokenizer required; encoded without special
    tokens), or a list of id-lists — the OpenAI surface adapted to the
    token-id API.

    String stops are encoded ONCE and matched at token level: a
    tokenizer that merges context differently (leading-space variants)
    can produce output text containing the string without the token
    tail ever matching. Pass token-id lists for exact control."""
    if value is None:
        return None
    if isinstance(value, str):
        value = [value]
    if not isinstance(value, list):
        raise ValueError("stop must be a string or a list")
    out = []
    for s in value:
        if isinstance(s, str):
            if tok is None:
                raise ValueError("string stop sequences need a tokenizer "
                                 "— start the server with --hf-model, or "
                                 "pass token-id lists")
            out.append(tok.encode(s, add_special_tokens=False))
        elif isinstance(s, list):
            out.append([int(t) for t in s])
        else:
            raise ValueError("each stop entry must be a string or id list")
    return out


def _parse_bool(value, field: str) -> bool:
    """Strict JSON-boolean field: every other sampling param funnels bad
    input to the 422 path, so `\"logprobs\": 5` (OpenAI's top-N form,
    unsupported) or \"false\" must not silently coerce to True."""
    if value is None:
        return False
    if isinstance(value, bool):
        return value
    raise ValueError(f"{field} must be a JSON boolean, got {value!r}")


class _StreamDecoder:
    """Incremental detokenization for SSE text deltas.

    Decoding each token prefix from scratch is O(n^2) per stream AND
    wrong for multi-byte characters (a UTF-8 char split across tokens
    decodes to U+FFFD until its last byte arrives, and the 'fixed'
    decode is not a string extension of the broken one). The standard
    fix: decode over a short sliding window [prefix:read) vs
    [prefix:], emit the extension only once it no longer ends in a
    replacement char, and advance the window — O(window) per token,
    deltas concatenate exactly to the final text (modulo a held-back
    tail the final event's fresh full decode supplies)."""

    def __init__(self, tok) -> None:
        self.tok = tok
        self.toks: list = []
        self.prefix = 0  # window start
        self.read = 0    # tokens already reflected in emitted text

    def push(self, token: int) -> str:
        self.toks.append(token)
        prev = self.tok.decode(self.toks[self.prefix:self.read],
                               skip_special_tokens=True)
        full = self.tok.decode(self.toks[self.prefix:],
                               skip_special_tokens=True)
        if full.endswith("�"):
            return ""  # mid-character: hold until it completes
        if len(full) > len(prev) and full.startswith(prev):
            self.prefix = self.read
            self.read = len(self.toks)
            return full[len(prev):]
        return ""


class _Handler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def log_message(self, fmt, *args):  # noqa: A003 — quiet
        pass

    @property
    def svc(self) -> _Service:
        return self.server.svc  # type: ignore[attr-defined]

    def _send(self, status: int, body: dict) -> None:
        payload = json.dumps(body).encode()
        self.send_response(status)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(payload)))
        self.end_headers()
        self.wfile.write(payload)

    def do_GET(self) -> None:  # noqa: N802
        if self.path == "/healthz":
            return self._send(200, {"ok": True})
        if self.path == "/stats":
            stats = self.svc.engine.stats()
            stats["ticks"] = self.svc.ticks
            return self._send(200, stats)
        if self.path == "/metrics":
            # Prometheus text format, matching the operator's exporter
            # conventions (docs/metrics.md) so one scrape config covers
            # operator and serving pods
            stats = self.svc.engine.stats()
            stats["ticks"] = self.svc.ticks
            lines = []
            for key, val in sorted(stats.items()):
                if not isinstance(val, (int, float)):
                    continue
                name = f"kubedl_serving_{key}"
                lines.append(f"# TYPE {name} gauge")
                lines.append(f"{name} {float(val)}")
            payload = ("\n".join(lines) + "\n").encode()
            self.send_response(200)
            self.send_header("Content-Type", "text/plain; version=0.0.4")
            self.send_header("Content-Length", str(len(payload)))
            self.end_headers()
            self.wfile.write(payload)
            return
        self._send(404, {"error": f"unknown path {self.path}"})

    def _stream_response(self, req, timeout: float = 300.0) -> None:
        """Server-sent events: one `data:` line per emitted token as the
        engine produces it, then a final summary event. Start the server
        with --decode-block 1 for true per-token latency (larger blocks
        emit in bursts of up to that many ticks). ANY handler exit
        before completion — disconnect, socket timeout, deadline —
        cancels the request so an abandoned stream doesn't keep its
        slot generating tokens nobody reads."""
        import time as _time

        tok = self.svc.tokenizer
        dec = _StreamDecoder(tok) if tok is not None else None
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.send_header("Cache-Control", "no-cache")
        # no Content-Length: the stream ends at EOF, so this connection
        # can't be reused — advertise that instead of chunked framing
        self.send_header("Connection", "close")
        self.end_headers()
        self.close_connection = True
        sent = 0
        # stop sequences trim the token tail when they match, so (a) any
        # token still within the longest stop's reach is HELD BACK until
        # the request finishes (else the stream would leak a partial
        # match the final result excludes), and (b) that same margin
        # keeps `sent` out of the region _emit may delete, preserving
        # the unlocked reader's safety
        margin = max((len(s) for s in req.stop_sequences), default=0)
        deadline = _time.monotonic() + timeout
        try:
            while True:
                done = req.done  # read BEFORE draining: no lost-wakeup
                toks = list(req.tokens)
                lps = list(req.token_logprobs)
                limit = len(toks) if done else max(len(toks) - margin, 0)
                while sent < limit:
                    event = {"token": toks[sent], "request_id": req.request_id}
                    if req.logprobs and sent < len(lps):
                        event["logprob"] = lps[sent]
                    if dec is not None:
                        event["text_delta"] = dec.push(toks[sent])
                    self.wfile.write(
                        b"data: " + json.dumps(event).encode() + b"\n\n")
                    sent += 1
                self.wfile.flush()
                if done:
                    final = {"done": True, "tokens": toks,
                             "request_id": req.request_id}
                    if req.error:
                        # engine-side failure (e.g. poisoned prefill):
                        # done with empty tokens and the reason attached
                        final["error"] = req.error
                    if req.logprobs:
                        final["logprobs"] = list(req.token_logprobs)
                    if tok is not None:
                        # fresh full decode: deltas held back for an
                        # incomplete multi-byte char still land here
                        final["text"] = tok.decode(
                            toks, skip_special_tokens=True)
                    self.wfile.write(
                        b"data: " + json.dumps(final).encode() + b"\n\n")
                    self.wfile.flush()
                    return
                if _time.monotonic() > deadline:
                    self.wfile.write(
                        b"data: " + json.dumps(
                            {"error": "generation timed out",
                             "request_id": req.request_id}).encode() + b"\n\n")
                    self.wfile.flush()
                    return
                self.svc.kick()
                _time.sleep(0.005)
        except (BrokenPipeError, ConnectionResetError):
            pass  # the finally clause frees the slot
        finally:
            if not req.done:
                # every abnormal exit path — disconnect, ETIMEDOUT or
                # any other OSError from the socket, deadline — must
                # free the slot for live clients
                self.svc.cancel([req])

    def do_POST(self) -> None:  # noqa: N802
        if self.path not in ("/generate", "/prefix", "/adapter"):
            return self._send(404, {"error": f"unknown path {self.path}"})
        try:
            length = int(self.headers.get("Content-Length", "0") or "0")
            body = json.loads(self.rfile.read(length))
        except (json.JSONDecodeError, ValueError) as e:
            return self._send(400, {"error": f"bad JSON: {e}"})
        if not isinstance(body, dict):
            return self._send(400, {"error": "body must be a JSON object"})
        if self.path == "/prefix":
            try:
                pid = self.svc.register_prefix(body.get("tokens") or [])
            except (ValueError, TypeError) as e:
                return self._send(422, {"error": str(e)})
            return self._send(200, {"prefix_id": pid})
        if self.path == "/adapter":
            alpha = body.get("alpha")
            try:
                aid = self.svc.register_adapter(
                    str(body.get("checkpoint_path") or ""),
                    alpha=None if alpha is None else float(alpha))
            except (ValueError, TypeError) as e:
                return self._send(422, {"error": str(e)})
            return self._send(200, {"adapter_id": aid})
        try:
            stream = _parse_bool(body.get("stream"), "stream")
        except ValueError as e:
            return self._send(422, {"error": str(e)})
        entries = body.get("requests")
        single = entries is None
        if single:
            entries = [body]
        if stream and not single:
            return self._send(422, {"error": "stream only supports the "
                                             "single-request form"})
        tok = self.svc.tokenizer
        reqs = []
        try:
            for e in entries:
                if not isinstance(e, dict):
                    raise ValueError("each request must be a JSON object")
                provided = [k for k in ("tokens", "text", "messages")
                            if e.get(k) is not None]
                if len(provided) > 1:
                    raise ValueError(
                        "pass exactly one of tokens / text / messages, "
                        f"got {'+'.join(provided)}")
                tokens = e.get("tokens")
                msgs = e.get("messages")
                is_text = tokens is None and e.get("text") is not None
                if msgs is not None:
                    # chat form: the tokenizer's own template renders the
                    # conversation (plus generation prompt) into ids
                    if tok is None:
                        raise ValueError(
                            "messages need a tokenizer — start the "
                            "server with --hf-model")
                    if not (isinstance(msgs, list) and msgs and all(
                            isinstance(m, dict) and "role" in m
                            and "content" in m for m in msgs)):
                        raise ValueError(
                            "messages must be a non-empty list of "
                            "{role, content} objects")
                    try:
                        tokens = tok.apply_chat_template(
                            msgs, add_generation_prompt=True, tokenize=True)
                    except Exception as exc:
                        # jinja TemplateError (e.g. a template's own
                        # raise_exception on bad role order) is not a
                        # ValueError — without this rewrap it would skip
                        # the 422 path AND the partial-batch cancel below
                        raise ValueError(
                            f"chat template failed: {exc}") from exc
                    is_text = True  # natural-stop eos default applies
                elif is_text:
                    if tok is None:
                        raise ValueError(
                            "text requests need a tokenizer — start the "
                            "server with --hf-model")
                    tokens = tok.encode(str(e["text"]))
                # eos default applies ONLY to text requests (natural stop);
                # the token-id API keeps exact-length semantics, and an
                # explicit "eos_token": null opts text requests out too
                if "eos_token" in e:
                    eos = e["eos_token"]
                elif is_text and tok is not None:
                    eos = tok.eos_token_id
                else:
                    eos = None
                temp = e.get("temperature")
                top_k = e.get("top_k")
                # explicit None checks: `or` would coerce the INVALID
                # top_p=0.0 to the default instead of letting the
                # engine's validation 422 it
                top_p = e.get("top_p")
                reqs.append(self.svc.submit(
                    tokens or [],
                    int(e.get("max_new_tokens") or 32),
                    eos,
                    prefix_id=e.get("prefix_id"),
                    temperature=None if temp is None else float(temp),
                    top_k=0 if top_k is None else int(top_k),
                    top_p=1.0 if top_p is None else float(top_p),
                    logprobs=_parse_bool(e.get("logprobs"), "logprobs"),
                    adapter_id=int(e.get("adapter_id") or 0),
                    stop=_parse_stop(e.get("stop"), tok),
                ))
        except (ValueError, TypeError) as e:
            # partially-submitted batch: release what already went in
            self.svc.cancel(reqs)
            return self._send(422, {"error": str(e)})
        if stream:
            return self._stream_response(reqs[0])
        if not self.svc.wait(reqs):
            # client gets a 504 and is gone; orphaned work must not keep
            # occupying slots generating tokens nobody reads
            self.svc.cancel(reqs)
            return self._send(504, {"error": "generation timed out"})
        results = []
        for r in reqs:
            entry = {"tokens": r.tokens, "request_id": r.request_id}
            if r.error:
                # engine-side failure (e.g. poisoned prefill batch): the
                # request is done with empty tokens; say why instead of
                # returning a silent empty completion
                entry["error"] = r.error
            if r.logprobs:
                entry["logprobs"] = r.token_logprobs
            if tok is not None:
                entry["text"] = tok.decode(r.tokens, skip_special_tokens=True)
            results.append(entry)
        self._send(200, results[0] if single else {"results": results})


def main(argv=None) -> int:
    args = parse_args(argv)

    from kubedl_tpu.train import coordinator

    coordinator.initialize()

    import jax

    # the engine's programs compile on first use, on whichever thread
    # ticks it: with the trace env injected each shows in `kubedl-tpu
    # trace` as jax.trace / jax.lower / jax.compile (obs/compiles.py,
    # whose own tracer is the injected env's)
    from kubedl_tpu.obs import compiles

    compiles.install()

    from kubedl_tpu.models.serving import ServingEngine
    from kubedl_tpu.train.generate import resolve_params

    params, config = resolve_params(
        args.model, args.hf_model, args.checkpoint_path,
        args.allow_fresh_init, lora_checkpoint_path=args.lora_checkpoint_path,
        lora_alpha=args.lora_alpha)
    if params is None:
        return 1
    from kubedl_tpu.train.generate import load_tokenizer

    tokenizer = load_tokenizer(args.hf_model)
    if args.int8:
        from kubedl_tpu.models import quant

        params = jax.jit(quant.quantize_params)(params)
    draft_params = draft_config = None
    if args.draft_model or args.draft_hf_model or args.draft_checkpoint_path:
        if not (args.draft_hf_model or args.draft_checkpoint_path):
            # resolve_params would silently fresh-init a weightless
            # draft; random drafts floor acceptance and make serving
            # STRICTLY slower than the plain engine
            if not args.allow_fresh_init:
                print("error: --draft-model needs weights "
                      "(--draft-checkpoint-path or --draft-hf-model); "
                      "pass --allow-fresh-init to force a random draft "
                      "for tests", file=sys.stderr)
                return 1
            print("warning: random-init draft — speculation will be "
                  "slower than plain serving (test mode)", file=sys.stderr)
        draft_params, draft_config = resolve_params(
            args.draft_model or "tiny", args.draft_hf_model,
            args.draft_checkpoint_path, args.allow_fresh_init,
            label="draft")
        if draft_params is None:
            return 1
    engine = ServingEngine(
        params, config, slots=args.slots, max_len=args.max_len,
        temperature=args.temperature,
        kv_dtype="int8" if args.kv_int8 else None,
        draft_params=draft_params, draft_config=draft_config,
        spec_k=args.spec_k,
    )
    svc = _Service(engine, tokenizer=tokenizer, decode_block=args.decode_block)
    for spec in args.adapter:
        # CKPT[:ALPHA] — registration failures at startup are fatal: a
        # deployment that silently dropped an adapter would 422 every
        # request that names it
        path, _, alpha_s = spec.rpartition(":")
        if path and alpha_s.replace(".", "", 1).isdigit():
            alpha = float(alpha_s)
        else:
            path, alpha = spec, None
        try:
            aid = svc.register_adapter(path, alpha=alpha)
        except ValueError as e:
            print(f"error: --adapter {spec!r}: {e}", file=sys.stderr)
            svc.stop()
            return 1
        print(f"adapter {aid}: {path} (alpha={alpha})", flush=True)
    httpd = ThreadingHTTPServer((args.bind, args.port), _Handler)
    httpd.daemon_threads = True
    httpd.svc = svc  # type: ignore[attr-defined]
    host, port = httpd.server_address[:2]
    model_name = args.hf_model or args.model
    print(f"serving {model_name} on http://{host}:{port} "
          f"(slots={args.slots}, max_len={args.max_len})", flush=True)
    if args.max_steps:
        # smoke mode: serve in the background until N ticks happen
        import time

        t = threading.Thread(target=httpd.serve_forever, daemon=True)
        t.start()
        while svc.ticks < args.max_steps:
            time.sleep(0.05)
        httpd.shutdown()
        svc.stop()
        return 0
    try:
        httpd.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        svc.stop()
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
