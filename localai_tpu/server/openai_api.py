"""OpenAI-compatible endpoints + LocalAI native endpoints.

Reference: core/http/endpoints/openai/*.go (chat.go:27 SSE+tools,
completion.go, edit.go, embeddings.go, list.go) and endpoints/localai
(tokenize.go, system.go, backend.go monitor/shutdown). Handlers translate
HTTP requests into engine GenRequests; the streaming path iterates the
engine's per-request event queue directly into SSE frames.
"""

from __future__ import annotations

import queue
import threading
import time
import uuid
from typing import Any, Callable, Iterator, Optional

from localai_tpu import __version__
from localai_tpu.config import LoraConfigError, Usecase
from localai_tpu.engine import AdapterError, GenRequest, QueueFullError
from localai_tpu.server.app import (
    ApiError,
    RawStream,
    Request,
    Response,
    Router,
    SSEStream,
)
from localai_tpu.server.manager import (
    LoadedModel,
    ModelManager,
    ModelQuarantinedError,
)


def _now() -> int:
    return int(time.time())


def _extract_images(messages: list) -> list:
    """Decode image_url content parts (data: URIs) → uint8 arrays
    (reference: message.go content-part parsing feeding multimodal
    backends)."""
    import base64
    import io

    out = []
    for m in messages:
        content = m.get("content")
        if not isinstance(content, list):
            continue
        for part in content:
            if not isinstance(part, dict) or part.get("type") != "image_url":
                continue
            url = (part.get("image_url") or {}).get("url", "")
            if not url.startswith("data:"):
                continue  # zero-egress: only inline data URIs
            try:
                raw = base64.b64decode(url.split(",", 1)[-1])
                from PIL import Image
                import numpy as np

                out.append(np.asarray(Image.open(io.BytesIO(raw)).convert("RGB")))
            except Exception:  # noqa: BLE001 — bad image part is skipped
                continue
    return out


def _fingerprint() -> str:
    return f"localai-tpu-{__version__}"


class OpenAIApi:
    def __init__(self, manager: ModelManager):
        self.manager = manager
        self.started_at = time.time()
        # Set by register(): the router carries the Metrics registry
        # (create_server attaches it) that the per-model lifecycle
        # histograms (ttft/inter_token/queue_wait/admit, ISSUE 11) feed.
        self.router: Optional[Router] = None

    # ------------------------------------------------------------------ #
    # Routing
    # ------------------------------------------------------------------ #

    def register(self, r: Router) -> None:
        self.router = r
        for prefix in ("/v1", ""):
            r.add("POST", f"{prefix}/chat/completions", self.chat)
            r.add("POST", f"{prefix}/completions", self.completion)
            r.add("POST", f"{prefix}/edits", self.edit)
            r.add("POST", f"{prefix}/embeddings", self.embeddings)
            r.add("GET", f"{prefix}/models", self.list_models)
        r.add("GET", "/v1/models/:name", self.get_model)
        r.add("POST", "/v1/tokenize", self.tokenize)
        r.add("POST", "/tokenize", self.tokenize)
        r.add("GET", "/healthz", self.health)
        r.add("GET", "/readyz", self.health)
        r.add("GET", "/version", self.version)
        r.add("GET", "/system", self.system)
        r.add("GET", "/backend/monitor", self.backend_monitor)
        r.add("POST", "/backend/monitor", self.backend_monitor)
        r.add("POST", "/backend/shutdown", self.backend_shutdown)
        # Cluster control plane (ISSUE 6, docs/CLUSTER.md): role/status
        # introspection plus the KV-span transfer seam — a prefill-role
        # worker answers /cluster/span/export with a versioned binary frame
        # and a decode-role worker lands it via /cluster/span/import, which
        # is all a network-hop disaggregation deployment needs.
        r.add("GET", "/cluster/status", self.cluster_status)
        r.add("POST", "/cluster/span/export", self.cluster_span_export)
        r.add("POST", "/cluster/span/import", self.cluster_span_import)
        # Elastic membership (ISSUE 19, docs/CLUSTER.md "Membership
        # lifecycle"): join a remote worker at runtime, drain a member
        # (in-flight streams finish, no new picks), leave gracefully
        # (drain, then removal once in-flight hits zero).
        for prefix in ("/v1", ""):
            r.add("POST", f"{prefix}/cluster/join", self.cluster_join)
            r.add("POST", f"{prefix}/cluster/drain", self.cluster_drain)
            r.add("POST", f"{prefix}/cluster/leave", self.cluster_leave)
        # Request-lifecycle observability (ISSUE 11, docs/OBSERVABILITY.md):
        # per-request span trees (W3C traceparent propagated), the engine
        # journal as Perfetto-loadable Chrome trace JSON, and an opt-in
        # jax.profiler capture window (LOCALAI_PROFILE gates it).
        r.add("GET", "/debug/trace/:request_id", self.debug_trace)
        r.add("GET", "/debug/timeline", self.debug_timeline)
        r.add("POST", "/debug/profile", self.debug_profile)
        # Engine gauges (kv pages free/total, queue depth, preemptions,
        # swap bytes, prefix host tier, ...) ride the Prometheus scrape as
        # localai_engine_*{model=...} — create_server polls this at every
        # /metrics render (previously reachable only via the JSON
        # backend-monitor endpoint).
        r.gauge_source = self.engine_gauges

    # ------------------------------------------------------------------ #
    # Helpers
    # ------------------------------------------------------------------ #

    def _resolve_name(self, req: Request, usecase: Usecase) -> str:
        """Model resolution tiers mirroring the reference extractor
        (middleware/request.go:47-92): body → route param → query param →
        bearer token naming a configured model → first config serving the
        usecase."""
        body = req.body or {}
        name = body.get("model") or (req.params or {}).get("name")
        if not name:
            name = (req.query.get("model") or [None])[0]
        if not name:
            auth = req.headers.get("authorization", "")
            token = auth[7:] if auth.startswith("Bearer ") else ""
            if token and self.manager.configs.get(token) is not None:
                name = token
        if not name:
            cfg = self.manager.configs.first_with(usecase)
            if cfg is None:
                raise ApiError(404, f"no model configured for {usecase}")
            name = cfg.name
        cfg = self.manager.configs.get(name)
        if cfg is None:
            raise ApiError(404, f"model {name!r} not found")
        if not cfg.has_usecase(usecase):
            raise ApiError(400, f"model {name!r} does not support {usecase}")
        return name

    def _resolve(self, req: Request, usecase: Usecase):
        """Loaded model + idempotent lease, taken atomically w.r.t. eviction."""
        name = self._resolve_name(req, usecase)
        try:
            return self.manager.lease(name)
        except KeyError:
            raise ApiError(404, f"model {name!r} not found") from None
        except LoraConfigError as e:
            # Contradictory virtual-model / merge-at-load setup (ISSUE 10):
            # a clean 400 for this one model, serving stays up.
            raise ApiError(400, str(e)) from None
        except ModelQuarantinedError as e:
            # Crash-only supervision tripped its restart budget (ISSUE 4):
            # a clean 503 with the remaining quarantine window, not a
            # respawn loop.
            raise ApiError(
                503, str(e), "server_error", retry_after=e.retry_after_s
            ) from None

    @staticmethod
    def _submit_all(lm: LoadedModel, gens: list, group: int = 1) -> list:
        """Submit every GenRequest, mapping engine backpressure to HTTP:
        a full queue (QueueFullError) becomes 429 + Retry-After derived
        from the engine's observed admission latency, and any handles
        already submitted are cancelled so a partially-admitted multi-
        choice request never leaks slots.

        `group` > 1 routes each run of `group` consecutive same-prompt
        requests (one n>1 / best_of choice group) through ONE fork
        admission (ISSUE 18, docs/TREE_SAMPLING.md): the group pays a
        single prefill and the engine forks the slot CoW per branch.
        Engines without the fork surface (remote proxies, cluster
        facades) and conditions the engine can't fork (dense cache,
        draft model, fork_sampling off) fall back to independent clone
        submits with identical outputs."""
        handles = []
        try:
            if group > 1 and hasattr(lm.engine, "submit_fork"):
                for k in range(0, len(gens), group):
                    handles.extend(lm.engine.submit_fork(gens[k:k + group]))
            else:
                for g in gens:
                    handles.append(lm.engine.submit(g))
        except QueueFullError as e:
            for h in handles:
                h.cancel()
            raise ApiError(
                429, str(e), "rate_limit_exceeded",
                retry_after=e.retry_after_s,
            ) from None
        except AdapterError as e:
            # Tenant-identity failure (ISSUE 10): the adapter vanished
            # between resolution and submit, or the base cannot serve it.
            for h in handles:
                h.cancel()
            raise ApiError(400, str(e)) from None
        return handles

    def _proxy_remote(self, req: Request, lm: LoadedModel, lease) -> Response | SSEStream:
        """Relay a request to an out-of-process backend (backend: remote or
        subprocess — the L7 seam; reference: every backend is a separate
        gRPC process, initializers.go:50-154)."""
        import urllib.error

        eng = lm.engine
        stream = bool((req.body or {}).get("stream"))
        try:
            # Per-call deadline (ISSUE 19): the request's own remaining
            # budget bounds the proxy socket instead of a flat 600 s —
            # body deadline_s, else the model's configured deadline.
            deadline = float((req.body or {}).get("deadline_s")
                             or getattr(lm.cfg, "deadline_s", 0.0) or 0.0)
        except (TypeError, ValueError):
            deadline = 0.0
        try:
            resp = eng.request(req.path, req.body, method=req.method,
                               deadline_s=deadline)
        except urllib.error.HTTPError as e:
            body = e.read()
            lease.release()
            return Response(
                status=e.code, body=body,
                content_type=e.headers.get("Content-Type", "application/json"),
            )
        except Exception as e:  # noqa: BLE001
            lease.release()
            raise ApiError(502, f"remote backend failed: {e}", "server_error") from None
        if stream and "event-stream" in (resp.headers.get("Content-Type") or ""):
            def events():
                try:
                    for raw in resp:
                        line = raw.decode("utf-8", "replace").strip()
                        if line.startswith("data: "):
                            payload = line[6:]
                            if payload != "[DONE]":  # our writer adds its own
                                yield payload
                finally:
                    resp.close()
                    lease.release()

            return SSEStream(events())
        try:
            data = resp.read()
        finally:
            resp.close()
            lease.release()
        return Response(
            body=data, content_type=resp.headers.get("Content-Type", "application/json")
        )

    def _gen_request(self, lm: LoadedModel, body: dict[str, Any], prompt_ids: list[int],
                     extra_stop: Optional[list[str]] = None) -> GenRequest:
        cfg = lm.cfg
        stop = body.get("stop") or []
        if isinstance(stop, str):
            stop = [stop]
        stop = list(stop) + [s for s in (extra_stop or []) if s not in stop]
        max_tokens = body.get("max_completion_tokens") or body.get("max_tokens") or cfg.max_tokens

        def pick(key: str, default):
            v = body.get(key)
            return default if v is None else v

        logit_bias = {}
        for k, v in (body.get("logit_bias") or {}).items():
            try:
                logit_bias[int(k)] = float(v)
            except (TypeError, ValueError):
                raise ApiError(400, f"invalid logit_bias entry {k!r}") from None

        return GenRequest(
            prompt_ids=prompt_ids,
            max_new_tokens=int(max_tokens),
            temperature=float(pick("temperature", cfg.temperature)),
            top_k=int(pick("top_k", cfg.top_k)),
            top_p=float(pick("top_p", cfg.top_p)),
            min_p=float(pick("min_p", cfg.min_p)),
            repeat_penalty=float(pick("repeat_penalty", cfg.repeat_penalty)),
            presence_penalty=float(pick("presence_penalty", cfg.presence_penalty)),
            frequency_penalty=float(pick("frequency_penalty", cfg.frequency_penalty)),
            stop=stop,
            seed=body.get("seed", cfg.seed),
            logit_bias=logit_bias,
            # vLLM-style extension: benchmarking/testing wants fixed-length
            # generations regardless of what the model samples.
            ignore_eos=bool(body.get("ignore_eos", False)),
            # End-to-end deadline (ISSUE 4): body overrides the model
            # YAML's default; past it, pending requests shed and active
            # ones cancel (docs/ROBUSTNESS.md).
            deadline_s=float(pick("deadline_s", cfg.deadline_s)),
            # Multi-tenant LoRA (ISSUE 10): a virtual model resolves to
            # the base's shared engine + this tenant's adapter name.
            adapter=getattr(lm, "adapter", None),
        )

    @staticmethod
    def _n_choices(body: dict[str, Any]) -> int:
        n = body.get("n") or 1
        try:
            n = int(n)
        except (TypeError, ValueError):
            raise ApiError(400, "n must be an integer") from None
        if n < 1 or n > 64:
            raise ApiError(400, "n must be between 1 and 64")
        return n

    @staticmethod
    def _best_of(body: dict[str, Any], n: int) -> int:
        """Validated `best_of` branch count (docs/TREE_SAMPLING.md):
        generate best_of branches off one shared prefill, rank by
        cumulative logprob, return the top n. Defaults to n (no
        over-generation); streaming cannot rank after the fact, so
        best_of > n on a stream is a client error (OpenAI semantics)."""
        bo = body.get("best_of")
        if bo is None:
            return n
        try:
            bo = int(bo)
        except (TypeError, ValueError):
            raise ApiError(400, "best_of must be an integer") from None
        if bo < n:
            raise ApiError(400, "best_of must be >= n")
        if bo > 64:
            raise ApiError(400, "best_of must be between n and 64")
        if bo > n and body.get("stream"):
            raise ApiError(400, "best_of > n cannot be used with streaming")
        return bo

    @staticmethod
    def _select_best(results: list, n: int) -> list:
        """best_of ranking for one choice group: highest cumulative token
        logprob first (ties keep submission order), top n re-indexed in
        rank order."""
        def score(r) -> float:
            return sum(ev.logprob for ev in r[1] if ev.logprob is not None)

        order = sorted(range(len(results)),
                       key=lambda i: (-score(results[i]), i))
        return [results[i] for i in order[:n]]

    @staticmethod
    def _merge_streams(handles: list) -> Iterator[tuple[int, Any]]:
        """Interleave events from several engine handles as (index, event).

        Each handle is drained by its own reader thread into one queue, so
        slow consumers of one choice never stall the engine-side queues of
        the others (multi-slot fan-out for n>1 — reference: the proto's
        one-stream-per-call model never needed this; slots make it natural).
        """
        if len(handles) == 1:
            for ev in handles[0]:
                yield 0, ev
            return
        q: "queue.Queue[tuple[int, Any]]" = queue.Queue()

        def reader(idx: int, h) -> None:
            for ev in h:
                q.put((idx, ev))

        for idx, h in enumerate(handles):
            threading.Thread(target=reader, args=(idx, h), daemon=True,
                             name=f"stream-reader-{idx}").start()
        done = 0
        while done < len(handles):
            idx, ev = q.get()
            if ev.kind in ("done", "error"):
                done += 1
            yield idx, ev

    @staticmethod
    def _collect(handle) -> tuple[str, list, Any]:
        """Drain one handle → (text, token events, final event)."""
        parts: list[str] = []
        toks: list = []
        final = None
        for ev in handle:
            if ev.kind == "token":
                parts.append(ev.text)
                toks.append(ev)
            elif ev.kind == "error":
                raise ApiError(500, ev.error)
            else:
                final = ev
        return "".join(parts), toks, final

    @staticmethod
    def _tag_requests(gens: list, rid: str, traceparent: str) -> None:
        """Stamp lifecycle-tracing identity onto each GenRequest (ISSUE 11):
        the response id keys /debug/trace/{id} (choice i > 0 gets `-i`),
        and a traceparent is minted when the client sent none so every
        leg — cluster replicas, disaggregated prefill/decode — shares one
        trace id."""
        from localai_tpu.observe.trace import new_traceparent, parse_traceparent

        if not (traceparent and parse_traceparent(traceparent)):
            traceparent = new_traceparent()
        for i, g in enumerate(gens):
            g.request_id = rid if i == 0 else f"{rid}-{i}"
            g.traceparent = traceparent

    def _note_request_metrics(self, model_name: str, finals: list) -> None:
        """Feed the per-model lifecycle histograms (ISSUE 11) from the
        terminal events' timing fields. No-op when the router has no
        Metrics yet (unit tests that call handlers directly)."""
        m = getattr(self.router, "metrics", None) if self.router else None
        if m is None:
            return
        labels = {"model": model_name}
        for f in finals:
            if f is None or getattr(f, "kind", "done") != "done":
                continue
            m.observe("queue_wait", f.timing_queue_wait, labels)
            m.observe("admit", f.timing_prompt_processing, labels)
            m.observe(
                "ttft", f.timing_queue_wait + f.timing_prompt_processing,
                labels,
            )
            if f.completion_tokens > 1 and f.timing_token_generation > 0:
                m.observe(
                    "inter_token",
                    f.timing_token_generation / (f.completion_tokens - 1),
                    labels,
                )

    @staticmethod
    def _sum_usage(finals: list, extra: bool) -> dict[str, Any]:
        pt = sum(f.prompt_tokens for f in finals)
        ct = sum(f.completion_tokens for f in finals)
        u = {"prompt_tokens": pt, "completion_tokens": ct, "total_tokens": pt + ct}
        if extra:
            u["timing_prompt_processing"] = sum(f.timing_prompt_processing for f in finals)
            u["timing_token_generation"] = sum(f.timing_token_generation for f in finals)
            u["timing_queue_wait"] = sum(f.timing_queue_wait for f in finals)
        return u

    def _chat_logprobs(self, body: dict[str, Any]) -> int:
        """Parsed chat logprobs request: 0 = off, else top-N to return."""
        if not body.get("logprobs"):
            return 0
        top = body.get("top_logprobs")
        top = 1 if top is None else int(top)
        if top < 0 or top > 20:
            raise ApiError(400, "top_logprobs must be between 0 and 20")
        return max(top, 1)

    @staticmethod
    def _lp_entry(lm, ev) -> dict[str, Any]:
        """One OpenAI chat logprobs content entry from a token event."""
        s = lm.engine.token_text(ev.token_id)
        return {
            "token": s,
            "logprob": ev.logprob,
            "bytes": list(s.encode("utf-8")),
            "top_logprobs": [
                {
                    "token": lm.engine.token_text(i),
                    "logprob": v,
                    "bytes": list(lm.engine.token_text(i).encode("utf-8")),
                }
                for i, v in (ev.top_logprobs or [])
            ],
        }

    def _chat_lp_content(self, lm, tok_events: list) -> dict[str, Any]:
        return {
            "content": [
                self._lp_entry(lm, ev) for ev in tok_events if ev.logprob is not None
            ]
        }

    @staticmethod
    def _usage(final, extra: bool) -> dict[str, Any]:
        u = {
            "prompt_tokens": final.prompt_tokens,
            "completion_tokens": final.completion_tokens,
            "total_tokens": final.prompt_tokens + final.completion_tokens,
        }
        if extra:
            # reference: Extra-Usage header surfaces backend timings
            # (chat.go:47-50; proto Reply timing fields).
            u["timing_prompt_processing"] = final.timing_prompt_processing
            u["timing_token_generation"] = final.timing_token_generation
            u["timing_queue_wait"] = final.timing_queue_wait
        return u

    # ------------------------------------------------------------------ #
    # Chat
    # ------------------------------------------------------------------ #

    def chat(self, req: Request) -> Response | SSEStream:
        body = req.body or {}
        messages = body.get("messages")
        if not messages or not isinstance(messages, list):
            raise ApiError(400, "messages is required and must be a non-empty array")
        lm, lease = self._resolve(req, Usecase.CHAT)
        from localai_tpu.engine.remote import RemoteEngine

        if isinstance(lm.engine, RemoteEngine):
            return self._proxy_remote(req, lm, lease)
        try:
            return self._chat_inner(req, lm, lease, body)
        except BaseException:
            lease.release()  # idempotent — safe even if the inner path released
            raise

    @staticmethod
    def _gbnf_factory(body: dict[str, Any]) -> Optional[Callable[[], Any]]:
        """Factory for a raw `grammar` (GBNF) body field, or None. Malformed
        grammars — including pathological depth — are a 400, not a 500."""
        gbnf_text = body.get("grammar")
        if not (isinstance(gbnf_text, str) and gbnf_text.strip()):
            return None
        from localai_tpu.functions.gbnf import (
            CompiledGrammar,
            GbnfConstraint,
            GbnfParseError,
        )

        try:
            compiled = CompiledGrammar(gbnf_text)
        except (GbnfParseError, RecursionError, MemoryError) as e:
            raise ApiError(400, f"invalid grammar: {e}") from None
        return lambda: GbnfConstraint(compiled)

    def _chat_inner(self, req: Request, lm: LoadedModel, lease, body: dict[str, Any]) -> Response | SSEStream:
        from localai_tpu.functions import tools_prompt_for, parse_function_calls
        from localai_tpu.functions.jsonschema import GrammarConstraint, tool_call_schema

        tools = body.get("tools") or []
        if body.get("functions"):  # legacy field
            tools = [{"type": "function", "function": f} for f in body["functions"]]
        tool_choice = body.get("tool_choice")
        if tool_choice == "none":
            tools = []
        tprompt = tools_prompt_for(tools) if tools else ""

        # Constrained decoding (reference: chat.go:224-253 grammar generation
        # for tools / response_format; here a token-mask grammar). A factory,
        # not an instance: the pushdown machine is mutable per-request state,
        # and n>1 needs one machine per choice.
        make_grammar: Optional[Callable[[], Any]] = None
        rf = body.get("response_format") or {}
        if rf.get("type") == "json_object":
            make_grammar = lambda: GrammarConstraint({"type": "object"})
        elif rf.get("type") == "json_schema":
            schema = (rf.get("json_schema") or {}).get("schema") or {}
            make_grammar = lambda: GrammarConstraint(schema)
        if tools and (tool_choice == "required" or isinstance(tool_choice, dict)):
            selected = tools
            if isinstance(tool_choice, dict):
                fname = (tool_choice.get("function") or {}).get("name")
                named = [t for t in tools if (t.get("function") or {}).get("name") == fname]
                if not named:
                    raise ApiError(400, f"tool_choice names unknown function {fname!r}")
                selected = named
            make_grammar = lambda: GrammarConstraint(tool_call_schema(selected))
        # Raw GBNF grammar (reference: backend.proto:139 `Grammar` forwarded
        # verbatim to llama.cpp). Checked LAST: an explicit grammar takes
        # precedence over response_format AND tool_choice, like the
        # reference passes an explicit grammar through untouched.
        make_grammar = self._gbnf_factory(body) or make_grammar

        prompt = lm.evaluator.template_messages(body["messages"], tools_prompt=tprompt)
        add_bos = not lm.cfg.template.use_tokenizer_template
        ids = lm.engine.tokenizer.encode(prompt, add_bos=add_bos)
        n = self._n_choices(body)
        lp_n = self._chat_logprobs(body)

        # Multimodal: project the first image and reserve a placeholder span
        # right after BOS (llava injection — models/vision.py). Qwen2-VL
        # encoders additionally yield the native-resolution grid, from which
        # the 3D m-rope position streams are derived
        # (models/qwen2_vl.mrope_positions_for_span).
        image_embeds = None
        image_offset = 0
        mrope_positions = None
        images = _extract_images(body["messages"])
        vision = getattr(lm, "vision", None)
        if images and vision is not None:
            image_offset = 1 if (add_bos and ids) else 0
            grid = None
            if getattr(vision, "kind", "") == "qwen2_vl":
                image_embeds, grid = vision.encode_with_grid(images[0])
            else:
                image_embeds = vision.encode(images[0])
            filler = [0] * image_embeds.shape[0]
            ids = ids[:image_offset] + filler + ids[image_offset:]
            if grid is not None:
                from localai_tpu.models.qwen2_vl import mrope_positions_for_span

                mrope_positions, _delta = mrope_positions_for_span(
                    len(ids), image_offset, grid, merge=vision.merge
                )

        # Independent GenRequest per branch: fresh grammar machine (the
        # pushdown state is mutable), decorrelated seeds when one was
        # given. best_of > n over-generates and ranks by cumulative
        # logprob, so ranking forces per-token logprobs internally (the
        # response strips them unless the client asked).
        bo = self._best_of(body, n)
        gens = []
        for i in range(bo):
            g = self._gen_request(lm, body, ids, extra_stop=lm.evaluator.stop_sequences())
            g.grammar = make_grammar() if make_grammar else None
            g.logprobs = lp_n if bo == n else max(lp_n, 1)
            g.image_embeds = image_embeds
            g.image_offset = image_offset
            g.mrope_positions = mrope_positions
            if g.seed is not None and bo > 1:
                g.seed = int(g.seed) + i
            gens.append(g)

        rid = f"chatcmpl-{uuid.uuid4().hex[:28]}"
        self._tag_requests(gens, rid, req.headers.get("traceparent", ""))
        created = _now()
        model_name = lm.cfg.name
        extra_usage = "extra-usage" in req.headers

        if body.get("stream"):
            handles = self._submit_all(lm, gens, group=len(gens))

            def cancel_all() -> None:
                for h in handles:
                    h.cancel()

            def events() -> Iterator[dict]:
                try:
                    base = {
                        "id": rid, "object": "chat.completion.chunk",
                        "created": created, "model": model_name,
                        "system_fingerprint": _fingerprint(),
                    }

                    def chunk(idx: int, delta: dict, finish=None, ev=None) -> dict:
                        c: dict[str, Any] = {"index": idx, "delta": delta, "finish_reason": finish}
                        if lp_n and ev is not None and ev.logprob is not None:
                            c["logprobs"] = {"content": [self._lp_entry(lm, ev)]}
                        return {**base, "choices": [c]}

                    for idx in range(n):
                        yield chunk(idx, {"role": "assistant", "content": ""})
                    finals: list[Any] = [None] * n
                    # Per-choice buffering state for tool-call detection:
                    # JSON/`<function=` heads buffer for parsing, anything
                    # else streams live (reference: chat.go streams function-
                    # call deltas, not raw JSON content).
                    st = [
                        {"parts": [], "events": [], "emitted": 0, "buffering": None}
                        for _ in range(n)
                    ]
                    for idx, ev in self._merge_streams(handles):
                        s = st[idx]
                        if ev.kind == "token":
                            s["parts"].append(ev.text)
                            s["events"].append(ev)
                            if not tools:
                                yield chunk(idx, {"content": ev.text}, ev=ev)
                                continue
                            if s["buffering"] is None:
                                head = "".join(s["parts"]).lstrip()
                                if head:
                                    s["buffering"] = head[0] in "{[<"
                            if s["buffering"] is False:
                                text = "".join(s["parts"][s["emitted"]:])
                                s["emitted"] = len(s["parts"])
                                yield chunk(idx, {"content": text}, ev=ev)
                        elif ev.kind == "error":
                            # A failed choice abandons the whole stream:
                            # cancel the siblings so their slots stop
                            # decoding into it (ISSUE 18 satellite).
                            cancel_all()
                            yield {"error": {"message": ev.error, "type": "server_error"}}
                            return
                        else:
                            finals[idx] = ev
                    done_finals = [f for f in finals if f is not None]
                    self._note_request_metrics(model_name, done_finals)
                    for idx in range(n):
                        s, final = st[idx], finals[idx]
                        if final is None:
                            continue
                        finish = final.finish_reason
                        if tools:
                            text = "".join(s["parts"])
                            if s["buffering"]:
                                calls = parse_function_calls(text, lm.cfg)
                                if calls:
                                    deltas = [{**c, "index": i} for i, c in enumerate(calls)]
                                    yield chunk(idx, {"tool_calls": deltas})
                                    finish = "tool_calls"
                                elif text:
                                    yield chunk(idx, {"content": text})
                            else:
                                tail = "".join(s["parts"][s["emitted"]:])
                                if tail:  # e.g. whitespace-only generation
                                    yield chunk(idx, {"content": tail})
                        out = chunk(idx, {}, finish=finish)
                        if idx == n - 1:
                            out["usage"] = self._sum_usage(done_finals, extra_usage)
                        yield out
                finally:
                    lease.release()

            return SSEStream(events(), on_disconnect=cancel_all)

        try:
            handles = self._submit_all(lm, gens, group=len(gens))
            try:
                results = [self._collect(h) for h in handles]
            except BaseException:
                for h in handles:
                    h.cancel()
                raise
        finally:
            lease.release()

        from localai_tpu.utils.finetune import finetune, needs_finetune

        # Usage/metrics count every generated branch (the client paid for
        # best_of completions); choices carry only the ranked top n.
        self._note_request_metrics(model_name, [r[2] for r in results])
        all_finals = [r[2] for r in results]
        if bo > n:
            results = self._select_best(results, n)
        choices = []
        for idx, (text, toks, final) in enumerate(results):
            if needs_finetune(lm.cfg):
                # Reference: Finetune post-processing on every prediction
                # (llm.go:217-265); the non-stream path only — streams are raw.
                text = finetune(lm.cfg, prompt, text)
            message: dict[str, Any] = {"role": "assistant", "content": text}
            finish = final.finish_reason
            if tools:
                calls = parse_function_calls(text, lm.cfg)
                if calls:
                    message = {"role": "assistant", "content": None, "tool_calls": calls}
                    finish = "tool_calls"
            choice: dict[str, Any] = {"index": idx, "message": message, "finish_reason": finish}
            if lp_n:
                choice["logprobs"] = self._chat_lp_content(lm, toks)
            choices.append(choice)
        return Response(body={
            "id": rid, "object": "chat.completion", "created": created,
            "model": model_name, "system_fingerprint": _fingerprint(),
            "choices": choices,
            "usage": self._sum_usage(all_finals, extra_usage),
        })

    # ------------------------------------------------------------------ #
    # Completion / edit
    # ------------------------------------------------------------------ #

    def completion(self, req: Request) -> Response | SSEStream:
        body = req.body or {}
        prompts = body.get("prompt", "")
        if isinstance(prompts, str):
            prompts = [prompts]
        if not prompts or not all(isinstance(p, str) for p in prompts):
            raise ApiError(400, "prompt must be a string or array of strings")
        lm, lease = self._resolve(req, Usecase.COMPLETION)
        from localai_tpu.engine.remote import RemoteEngine

        if isinstance(lm.engine, RemoteEngine):
            return self._proxy_remote(req, lm, lease)
        rid = f"cmpl-{uuid.uuid4().hex[:28]}"
        created = _now()
        extra_usage = "extra-usage" in req.headers
        try:
            return self._completion_inner(
                lm, lease, body, prompts, rid, created, extra_usage,
                traceparent=req.headers.get("traceparent", ""),
            )
        except BaseException:
            lease.release()
            raise

    def _completion_lp(self, body: dict[str, Any]) -> int:
        lp = body.get("logprobs")
        if lp is None or lp is False:
            return 0
        lp = 1 if lp is True else int(lp)
        if lp < 0 or lp > 20:
            raise ApiError(400, "logprobs must be between 0 and 20")
        return lp

    def _completion_lp_block(self, lm, toks: list, offset0: int) -> dict[str, Any]:
        """Legacy completions logprobs block for one choice."""
        tokens, token_lps, tops, offsets = [], [], [], []
        off = offset0
        for ev in toks:
            if ev.logprob is None:
                continue
            s = lm.engine.token_text(ev.token_id)
            tokens.append(s)
            token_lps.append(ev.logprob)
            tops.append({lm.engine.token_text(i): v for i, v in (ev.top_logprobs or [])})
            offsets.append(off)
            off += len(s)
        return {
            "tokens": tokens, "token_logprobs": token_lps,
            "top_logprobs": tops, "text_offset": offsets,
        }

    def _completion_inner(self, lm, lease, body, prompts, rid, created,
                          extra_usage, traceparent="") -> Response | SSEStream:
        n = self._n_choices(body)
        bo = self._best_of(body, n)
        lp_n = self._completion_lp(body)

        # Raw GBNF grammar on completions too (the reference's Grammar field
        # rides PredictOptions for every text endpoint).
        make_grammar = self._gbnf_factory(body)

        # One GenRequest per (prompt, branch): all submitted up front so
        # free slots run them concurrently (multi-prompt requests
        # previously ran serially — VERDICT weak #7); each prompt's
        # branches form one fork group (shared prefill). best_of > n
        # forces internal logprobs for the ranking pass.
        gens = []
        templated_prompts = []
        for p in prompts:
            templated = lm.evaluator.template_completion(p)
            templated_prompts.append(templated)
            ids = lm.engine.tokenizer.encode(templated, add_bos=True)
            for j in range(bo):
                g = self._gen_request(lm, body, ids)
                g.grammar = make_grammar() if make_grammar else None
                g.logprobs = lp_n if bo == n else max(lp_n, 1)
                if g.seed is not None and bo > 1:
                    g.seed = int(g.seed) + j
                gens.append(g)
        self._tag_requests(gens, rid, traceparent)

        if body.get("stream"):
            handles = self._submit_all(lm, gens, group=bo)

            def cancel_all() -> None:
                for h in handles:
                    h.cancel()

            def events() -> Iterator[dict]:
                base = {"id": rid, "object": "text_completion", "created": created,
                        "model": lm.cfg.name}
                try:
                    finals = [None] * len(handles)
                    for idx, ev in self._merge_streams(handles):
                        if ev.kind == "token":
                            c: dict[str, Any] = {"index": idx, "text": ev.text, "finish_reason": None}
                            if lp_n and ev.logprob is not None:
                                c["logprobs"] = self._completion_lp_block(lm, [ev], 0)
                            yield {**base, "choices": [c]}
                        elif ev.kind == "error":
                            # A failed choice abandons the whole stream:
                            # cancel the siblings so their slots stop
                            # decoding into it (ISSUE 18 satellite).
                            cancel_all()
                            yield {"error": {"message": ev.error, "type": "server_error"}}
                            return
                        else:
                            finals[idx] = ev
                    done = [f for f in finals if f is not None]
                    self._note_request_metrics(lm.cfg.name, done)
                    for idx, final in enumerate(finals):
                        if final is None:
                            continue
                        out = {**base, "choices": [{"index": idx, "text": "", "finish_reason": final.finish_reason}]}
                        if idx == len(finals) - 1:
                            out["usage"] = self._sum_usage(done, extra_usage)
                        yield out
                finally:
                    lease.release()

            return SSEStream(events(), on_disconnect=cancel_all)

        try:
            handles = self._submit_all(lm, gens, group=bo)
            try:
                results = [self._collect(h) for h in handles]
            except BaseException:
                for h in handles:
                    h.cancel()
                raise
        finally:
            lease.release()

        from localai_tpu.utils.finetune import finetune, needs_finetune

        # Usage/metrics count every generated branch (the client paid for
        # best_of completions); choices carry only each prompt's top n.
        self._note_request_metrics(lm.cfg.name, [r[2] for r in results])
        all_finals = [r[2] for r in results]
        if bo > n:
            results = [r for k in range(0, len(results), bo)
                       for r in self._select_best(results[k:k + bo], n)]
        choices = []
        for idx, (text, toks, final) in enumerate(results):
            prompt = prompts[idx // n]
            if needs_finetune(lm.cfg):
                text = finetune(lm.cfg, templated_prompts[idx // n], text)
            offset0 = 0
            # body-level echo (raw prompt) unless config echo already did it
            if body.get("echo") and not lm.cfg.echo:
                text = prompt + text
                offset0 = len(prompt)
            choice: dict[str, Any] = {"index": idx, "text": text, "finish_reason": final.finish_reason}
            if lp_n:
                choice["logprobs"] = self._completion_lp_block(lm, toks, offset0)
            choices.append(choice)
        return Response(body={
            "id": rid, "object": "text_completion", "created": created,
            "model": lm.cfg.name, "choices": choices,
            "usage": self._sum_usage(all_finals, extra_usage),
        })

    def edit(self, req: Request) -> Response:
        from localai_tpu.utils.finetune import finetune, needs_finetune

        body = req.body or {}
        instruction = body.get("instruction", "")
        if not instruction:
            raise ApiError(400, "instruction is required")
        lm, lease = self._resolve(req, Usecase.EDIT)
        try:
            prompt = lm.evaluator.template_edit(instruction, body.get("input", ""))
            ids = lm.engine.tokenizer.encode(prompt, add_bos=True)
            g = self._gen_request(lm, body, ids)
            self._tag_requests(
                [g], f"edit-{uuid.uuid4().hex[:28]}",
                req.headers.get("traceparent", ""),
            )
            text, final = self._submit_all(lm, [g])[0].result()
        finally:
            lease.release()
        self._note_request_metrics(lm.cfg.name, [final])
        if needs_finetune(lm.cfg):
            text = finetune(lm.cfg, prompt, text)
        return Response(body={
            "object": "edit", "created": _now(),
            "choices": [{"index": 0, "text": text}],
            "usage": self._usage(final, "extra-usage" in req.headers),
        })

    # ------------------------------------------------------------------ #
    # Embeddings / tokenize
    # ------------------------------------------------------------------ #

    def embeddings(self, req: Request) -> Response:
        body = req.body or {}
        inputs = body.get("input", "")
        if isinstance(inputs, str):
            inputs = [inputs]
        if not isinstance(inputs, list) or not inputs:
            raise ApiError(400, "input must be a non-empty string or array")
        lm, lease = self._resolve(req, Usecase.EMBEDDINGS)
        from localai_tpu.engine.remote import RemoteEngine

        if isinstance(lm.engine, RemoteEngine):
            return self._proxy_remote(req, lm, lease)
        try:
            tok = lm.engine.tokenizer
            ids_batch: list[list[int]] = []
            for item in inputs:
                if isinstance(item, str):
                    ids_batch.append(tok.encode(item) or [0])
                elif isinstance(item, list):  # pre-tokenized input
                    ids_batch.append([int(t) for t in item] or [0])
                else:
                    raise ApiError(400, "input items must be strings or token arrays")
            vecs = lm.engine.embed(ids_batch)
        finally:
            lease.release()
        n_tokens = sum(len(x) for x in ids_batch)
        return Response(body={
            "object": "list", "model": lm.cfg.name,
            "data": [
                {"object": "embedding", "index": i, "embedding": [float(x) for x in vec]}
                for i, vec in enumerate(vecs)
            ],
            "usage": {"prompt_tokens": n_tokens, "total_tokens": n_tokens},
        })

    def tokenize(self, req: Request) -> Response:
        body = req.body or {}
        content = body.get("content", "")
        lm, lease = self._resolve(req, Usecase.TOKENIZE)
        from localai_tpu.engine.remote import RemoteEngine

        if isinstance(lm.engine, RemoteEngine):
            return self._proxy_remote(req, lm, lease)
        try:
            ids = lm.engine.tokenizer.encode(content)
        finally:
            lease.release()
        return Response(body={"tokens": ids})

    # ------------------------------------------------------------------ #
    # Introspection
    # ------------------------------------------------------------------ #

    def list_models(self, req: Request) -> Response:
        data = [
            {"id": cfg.name, "object": "model", "created": _now(), "owned_by": "localai-tpu"}
            for cfg in self.manager.list_configs()
        ]
        return Response(body={"object": "list", "data": data})

    def get_model(self, req: Request) -> Response:
        name = req.params["name"]
        if self.manager.configs.get(name) is None:
            raise ApiError(404, f"model {name!r} not found")
        return Response(body={"id": name, "object": "model", "created": _now(), "owned_by": "localai-tpu"})

    def health(self, req: Request) -> Response:
        return Response(body={"status": "ok"})

    def version(self, req: Request) -> Response:
        return Response(body={"version": __version__})

    def system(self, req: Request) -> Response:
        import jax

        from localai_tpu.utils.sysinfo import (
            device_info,
            engine_placement,
            recommend_mesh,
        )

        loaded = self.manager.loaded_names()
        backends = {}
        placement = {}
        for n in loaded:
            lm = self.manager.peek(n)  # never trigger a load from a monitoring poll
            if lm is not None:
                backends[n] = lm.engine.metrics()
                placement[n] = engine_placement(lm.engine)
        return Response(body={
            "backends": backends,
            "placement": placement,
            "loaded_models": loaded,
            "configured_models": self.manager.configs.names(),
            "devices": [str(d) for d in jax.devices()],
            "sysinfo": device_info(),
            "recommended_mesh": recommend_mesh(),
            "uptime_s": time.time() - self.started_at,
            "version": __version__,
        })

    def engine_gauges(self):
        """(name, labels, value) triples for every loaded model's engine —
        the Prometheus face of Engine.metrics(). peek() only: a monitoring
        scrape must never trigger a model load."""
        out = []
        for n in self.manager.loaded_names():
            lm = self.manager.peek(n)
            if lm is None:
                continue
            try:
                gauges = lm.engine.metrics()
            except Exception:  # noqa: BLE001 — scrape survives a dying engine
                continue
            for k, v in gauges.items():
                labels = {"model": n}
                if k == "loop_dead":
                    # Flight recorder (ISSUE 11): a dead loop's gauge
                    # carries the postmortem path so the on-call can jump
                    # from the alert straight to the dump.
                    pm = getattr(lm.engine, "postmortem_path", "")
                    if pm:
                        labels["postmortem"] = pm
                out.append((f"localai_engine_{k}", labels, v))
        # Supervision gauges (ISSUE 4): restart / quarantine counters live
        # on the manager, not the (replaceable) engines.
        out.extend(self.manager.health_gauges())
        return out

    def backend_monitor(self, req: Request) -> Response:
        body = req.body or {}
        name = body.get("model") or (req.query.get("model") or [None])[0]
        if not name:
            raise ApiError(400, "model is required")
        lm = self.manager.peek(name)
        if lm is None:
            raise ApiError(404, f"model {name!r} is not loaded")
        return Response(body={
            "model": name,
            "metrics": lm.engine.metrics(),
            "loaded_for_s": time.monotonic() - lm.loaded_at,
            "in_flight": lm.in_flight,
            "supervision": self.manager.restart_stats(name),
        })

    def backend_shutdown(self, req: Request) -> Response:
        body = req.body or {}
        name = body.get("model")
        if not name:
            raise ApiError(400, "model is required")
        if not self.manager.unload(name):
            raise ApiError(404, f"model {name!r} is not loaded")
        return Response(body={"status": "ok"})

    # ------------------------------------------------------------------ #
    # Cluster control plane (ISSUE 6, docs/CLUSTER.md)
    # ------------------------------------------------------------------ #

    def cluster_status(self, req: Request) -> Response:
        app_cfg = self.manager.app_cfg
        engines = {}
        for n in self.manager.loaded_names():
            lm = self.manager.peek(n)
            if lm is None:
                continue
            client = getattr(lm.engine, "client", None)
            if client is not None:  # ClusterEngine fan-out
                engines[n] = {
                    "replicas": client.scheduler.snapshot(),
                    "metrics": client.metrics(),
                    # Membership/breaker/failover event tail (ISSUE 19) —
                    # what the chaos driver asserts its invariants from.
                    "events": client.scheduler.journal_events(last=100),
                }
        return Response(body={
            "role": app_cfg.cluster_role,
            "cluster_replicas": app_cfg.cluster_replicas,
            "cluster_peers": list(app_cfg.cluster_peers),
            "affinity_spans": app_cfg.affinity_spans,
            "transfer_max_bytes": app_cfg.transfer_max_bytes,
            "transfer_chunk_bytes": app_cfg.transfer_chunk_bytes,
            "engines": engines,
        })

    def _cluster_client(self, name: Optional[str]):
        """The ClusterClient behind a loaded cluster-served model (never
        triggers a load — membership changes on an unloaded model are
        meaningless; its cluster doesn't exist yet)."""
        if not name:
            raise ApiError(400, "model is required")
        lm = self.manager.peek(name)
        if lm is None:
            raise ApiError(404, f"model {name!r} is not loaded")
        client = getattr(lm.engine, "client", None)
        if client is None:
            raise ApiError(
                400, f"model {name!r} is not served by a cluster engine "
                     "(cluster_replicas >= 2 or cluster_peers required)")
        return client

    def cluster_join(self, req: Request) -> Response:
        """Runtime membership join (ISSUE 19): register a remote worker as
        a replica while traffic flows. The member enters the lifecycle at
        `joining` and becomes routable on its first successful gauge
        scrape — a joiner that never comes up never attracts traffic."""
        body = req.body or {}
        client = self._cluster_client(body.get("model"))
        name = str(body.get("name") or "").strip()
        url = str(body.get("url") or "").strip()
        if not name or not url:
            raise ApiError(400, "replica name and url are required")
        from localai_tpu.cluster.replica import RemoteReplica
        from localai_tpu.cluster.scheduler import ROLES

        role = str(body.get("role") or "mixed")
        if role not in ROLES:
            raise ApiError(400, f"cluster role {role!r} not in {ROLES}")
        if any(r.name == name for r in client.replicas):
            # Fast refusal before the (probing) RemoteReplica construction.
            raise ApiError(409, f"replica {name!r} is already a member",
                           kind="conflict")
        rep = RemoteReplica(
            name, url, role=role,
            model=str(body.get("remote_model") or body.get("model") or ""))
        # Check-and-register atomically: two concurrent joins with the same
        # name must not both pass the duplicate check — the loser 409s.
        with client._lock:
            if any(r.name == name for r in client.replicas):
                raise ApiError(409, f"replica {name!r} is already a member",
                               kind="conflict")
            client.replicas.append(rep)
            client.scheduler.add_replica(
                rep.name, target=rep, role=rep.role, gauge_fn=rep.gauges,
                dispatchable=False)
        # One immediate probe round so a ready worker serves from this
        # response on, not from the next natural gauge tick.
        client.scheduler.refresh(force=True)
        return Response(body={
            "joined": name,
            "state": client.scheduler.state(name),
            "replicas": client.scheduler.snapshot(),
        })

    def cluster_drain(self, req: Request) -> Response:
        """Drain a member: no NEW requests route to it, in-flight streams
        finish, and its span affinity moves to a survivor."""
        body = req.body or {}
        client = self._cluster_client(body.get("model"))
        name = str(body.get("name") or "").strip()
        if not name:
            raise ApiError(400, "replica name is required")
        if not client.scheduler.begin_drain(name):
            raise ApiError(404, f"replica {name!r} is not a drainable "
                                "member (unknown, dead, or removed)")
        return Response(body={
            "draining": name,
            "state": client.scheduler.state(name),
            "replicas": client.scheduler.snapshot(),
        })

    def cluster_leave(self, req: Request) -> Response:
        """Graceful removal: drain, then drop the member once its last
        in-flight stream ends (`force: true` removes immediately). The
        response reports the resulting state — "draining" means removal is
        deferred on live streams and completes automatically."""
        body = req.body or {}
        client = self._cluster_client(body.get("model"))
        name = str(body.get("name") or "").strip()
        if not name:
            raise ApiError(400, "replica name is required")
        state = client.scheduler.leave(name, force=bool(body.get("force")))
        if state == "removed":
            # The scheduler's table is the routing truth; the client's list
            # only feeds facade metrics — prune it for a clean status view.
            # Rebuild under the client lock so a concurrent join's append
            # is not lost to this list swap.
            with client._lock:
                client.replicas = [
                    r for r in client.replicas if r.name != name]
        return Response(body={
            "name": name,
            "state": state,
            "replicas": client.scheduler.snapshot(),
        })

    def _cluster_engine(self, name: Optional[str]):
        """A loaded engine with span transfer hooks (never triggers a
        load — transfer is an optimization, not worth paging a model in)."""
        if not name:
            raise ApiError(400, "model is required")
        lm = self.manager.peek(name)
        if lm is None:
            raise ApiError(404, f"model {name!r} is not loaded")
        eng = lm.engine
        if not hasattr(eng, "export_prefix_span"):
            # Cluster fan-out: export/import from the least-loaded live
            # replica is equivalent (spans are replica-local); use r0.
            reps = getattr(eng, "replicas", None)
            if reps:
                eng = reps[0].engine
        if not hasattr(eng, "export_prefix_span"):
            raise ApiError(400, f"model {name!r} has no KV span transfer "
                                "(paged LLM engines only)")
        return eng

    def cluster_span_export(self, req: Request) -> "Response | RawStream":
        """KV span out. Plain mode returns the raw LAIKV frame (back-compat
        with the ISSUE 6 single-host seam); `stream: true` (ISSUE 13)
        returns the chunked LAIKV-STREAM wire format — per-chunk CRC32s, a
        digest-pinned control header, and resume-from-`offset` support —
        and `compute: true` admits the prompt first when no span is stored
        yet (the remote-prefill entry point: one round trip computes AND
        streams the span)."""
        body = req.body or {}
        eng = self._cluster_engine(body.get("model"))
        prompt_ids = body.get("prompt_ids")
        if not isinstance(prompt_ids, list) or not prompt_ids:
            raise ApiError(400, "prompt_ids (non-empty token id list) required")
        app_cfg = self.manager.app_cfg
        pids = [int(t) for t in prompt_ids]
        trace = str(body.get("trace") or "")
        frame = eng.export_prefix_span(
            pids, max_bytes=app_cfg.transfer_max_bytes, trace_id=trace)
        if frame is None and body.get("compute"):
            # Prefill-on-demand: one probe admission saves the span in the
            # prefix cache (the same shape ClusterClient's in-process
            # handoff uses); it traces as the "<trace>:prefill" leg under
            # the caller's traceparent so a disaggregated request stays ONE
            # trace across machines (ISSUE 11/13).
            eng.generate(
                pids, max_new_tokens=1, ignore_eos=True,
                request_id=(trace + ":prefill") if trace else "",
                traceparent=req.headers.get("traceparent", ""))
            frame = eng.export_prefix_span(
                pids, max_bytes=app_cfg.transfer_max_bytes, trace_id=trace)
        if frame is None:
            raise ApiError(404, "no exportable span stored for this prompt")
        if not body.get("stream"):
            return Response(body=frame, content_type="application/octet-stream")
        from localai_tpu.cluster import netspan

        digest = netspan.frame_digest(frame)
        want = str(body.get("digest") or "")
        if want and want != digest:
            # The span was re-admitted/evicted between resume attempts —
            # the client must restart (or recompute), never splice frames.
            raise ApiError(409, "span changed since the transfer began",
                           kind="conflict")
        offset = int(body.get("offset") or 0)
        if offset < 0 or offset > len(frame):
            raise ApiError(400, f"offset {offset} outside the "
                                f"{len(frame)}-byte frame")
        chunk = int(body.get("chunk_bytes") or 0) or app_cfg.transfer_chunk_bytes
        return RawStream(
            netspan.encode_stream(frame, chunk_bytes=chunk, offset=offset,
                                  trace=trace),
            content_type="application/x-laikv-stream",
        )

    def cluster_span_import(self, req: Request) -> Response:
        """KV span in. Accepts a raw LAIKV frame (back-compat) or the
        LAIKV-STREAM wire format (detected by its chunk magic) — the
        latter is CRC/digest-verified chunk by chunk with the size cap
        enforced mid-walk, and a rejected stream reports `imported: false`
        plus the typed reason instead of landing corrupt KV."""
        name = (req.query.get("model") or [None])[0]
        eng = self._cluster_engine(name)
        if not req.raw_body:
            raise ApiError(400, "span frame bytes required as request body")
        app_cfg = self.manager.app_cfg
        raw = req.raw_body
        from localai_tpu.cluster import netspan
        from localai_tpu.cluster.transfer import SpanTransferError

        if raw[:len(netspan.CHUNK_MAGIC)] == netspan.CHUNK_MAGIC:
            try:
                raw, _meta = netspan.assemble(
                    raw, max_bytes=app_cfg.transfer_max_bytes,
                    verify=app_cfg.transfer_checksum)
            except SpanTransferError as e:
                return Response(body={"imported": False, "error": str(e)})
        ok = eng.import_span_bytes(
            raw, max_bytes=app_cfg.transfer_max_bytes
        )
        return Response(body={"imported": bool(ok)})

    # ------------------------------------------------------------------ #
    # Request-lifecycle observability (ISSUE 11, docs/OBSERVABILITY.md)
    # ------------------------------------------------------------------ #

    def debug_trace(self, req: Request) -> Response:
        """Span tree(s) for one request id — every leg the process saw
        (engine, cluster coordinator, disaggregated prefill), grouped by
        trace id. The id is the OpenAI response id (`chatcmpl-*`/`cmpl-*`,
        `-i` suffix for choice i > 0)."""
        from localai_tpu.observe.trace import STORE

        rid = req.params["request_id"]
        data = STORE.get_json(rid)
        if data is None:
            raise ApiError(
                404,
                f"no trace recorded for request {rid!r} (traces are kept "
                "for the most recent requests only)",
            )
        return Response(body=data)

    def _engine_journals(self, model: Optional[str]) -> dict:
        """{display name: EventJournal} across loaded engines (peek only —
        a debug pull must never trigger a model load). Cluster fan-outs
        contribute one journal per replica."""
        out: dict = {}
        for n in self.manager.loaded_names():
            if model and n != model:
                continue
            lm = self.manager.peek(n)
            if lm is None:
                continue
            eng = lm.engine
            journals = getattr(eng, "journals", None)
            if callable(journals):  # ClusterEngine: one row per replica
                for rname, j in journals().items():
                    out[f"{n}/{rname}"] = j
                continue
            j = getattr(eng, "journal", None)
            if j is not None:
                out[n] = j
        return out

    def debug_timeline(self, req: Request) -> Response:
        """The engine journal(s) as Chrome trace-event JSON — load the
        response body directly in Perfetto / chrome://tracing. `?model=`
        narrows to one model; cluster replicas render as process rows."""
        from localai_tpu.observe.timeline import chrome_trace

        model = (req.query.get("model") or [None])[0]
        journals = self._engine_journals(model)
        if not journals:
            raise ApiError(
                404,
                "no event journal available"
                + (f" for model {model!r}" if model else "")
                + " — is the model loaded and trace_journal_events > 0?",
            )
        return Response(body=chrome_trace(journals))

    def debug_profile(self, req: Request) -> Response:
        """Run one jax.profiler capture window (POST {"seconds": N}).
        Gated behind LOCALAI_PROFILE=<output dir>: profiling perturbs
        serving and writes device traces to disk, so it is an explicit
        operator opt-in."""
        import os

        from localai_tpu.observe import profile as oprofile

        prof_dir = os.environ.get("LOCALAI_PROFILE", "")
        if not prof_dir:
            raise ApiError(
                403,
                "profiling is disabled — set LOCALAI_PROFILE=<output dir> "
                "to allow /debug/profile capture windows",
            )
        seconds = float((req.body or {}).get("seconds", 1.0))
        try:
            result = oprofile.capture(prof_dir, seconds)
        except RuntimeError as e:
            raise ApiError(409, str(e)) from None
        # Mark the capture window in every journal so the timeline and the
        # profiler trace can be lined up.
        for j in self._engine_journals(None).values():
            j.stage("profile", a=result["seconds"])
        return Response(body={"status": "ok", **result})
