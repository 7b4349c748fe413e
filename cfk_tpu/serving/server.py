"""Request server: top-K queries over the transport's partitioned log.

The serving analog of the streaming consumer — requests arrive on a
``serve-requests`` topic (any Transport: InMemory for tests, FileBroker,
or the native TCP broker for cross-process serving), the server coalesces
everything currently pending into ONE scoring batch (bounded by
``max_batch``), runs it through the ``ServeEngine`` (whose pow2 batch
bucketing turns the coalesced sizes into a handful of compiled programs),
and answers on a ``serve-responses`` topic partition chosen by the client
(one partition per client — responses need no routing logic beyond the
partition, the same PureModPartitioner spirit as everything else).

Batching is the throughput lever, exactly as it was for the reference's
Kafka producer and PR 6's fold-in micro-batches: under open-loop load the
natural batch size self-tunes — a busy server finds more requests pending
per poll, amortizing the per-batch dispatch over more queries, which is
what makes the QPS-vs-latency trade measurable (PERF.md §4).

A request may name a department (``ScoreRequest.department``): its answer
is the exact top-K among that department's items, a scan of the
department's range of the engine's table.  One batch shares one scan, so a
batch holds requests of ONE department (or of none): a step polls one
partition, the one whose head has waited longest, and takes the run of
records at its head that name the same department.  The log does the
grouping where the topic is keyed by department (``ensure_serve_topics(
departments=)``, ``ServeClient(route="department")``: partition 0 for
requests that name none, partition 1 + d for department d), and every run
is then a full batch under a backlog; over any other partitioning the runs
are as long as the log happens to make them, and the answers the same.

Under a backlog the server keeps one batch in flight on the device
(``RecommendServer.step``): the batch just polled is staged and handed
over, then the batch handed over a step ago is fetched and answered, so
the scorer runs under the host's poll, assembly, upload and responses
instead of after them.  With no backlog a batch is answered in the step
that polled it.

A server with a ``StreamSession`` attached (``session=``) is the one loop
that drives both: before each poll it pumps the session — the micro-batches
on the device are committed and published to the engine, the next one is
handed over (several, where the stream has fallen behind its log) — so the
ratings a user sent are folded in between two request batches, and every
answer names the commit ordinal its batch saw.

That server is also the stream task's supervisor (``session_factory=``):
an exception out of ``session.pump`` ends THAT session, not the loop.  The
server abandons it (``StreamSession.abandon``: what it had in flight and
what its writer had not renamed is gone, as after a kill of the task),
keeps answering from the engine as the last published ordinal left it, and
brings up a successor from the store beside the loop (a thread: the resume
reads files and folds arrays, never the device); the step after the
successor is up pumps it, and it replays from the log what the dead one had
not made durable.
"""

from __future__ import annotations

import dataclasses
import sys
import threading
import time

import numpy as np

from cfk_tpu.serving.engine import TopKBatch, compute
from cfk_tpu.serving.topk_kernel import _pow2_ceil, range_tiles
from cfk_tpu.telemetry import get_tracer, record_event, span
from cfk_tpu.transport.serdes import (
    ScoreRequest,
    ScoreResponse,
    decode_score_request,
    decode_score_response,
    encode_score_request,
    encode_score_response,
)

REQUESTS_TOPIC = "serve-requests"
RESPONSES_TOPIC = "serve-responses"


def ensure_serve_topics(transport, *, requests_topic: str = REQUESTS_TOPIC,
                        responses_topic: str = RESPONSES_TOPIC,
                        request_partitions: int = 1,
                        response_partitions: int = 1,
                        departments: int | None = None) -> None:
    """Create the serve topics if absent (existing ones keep their own
    partition counts, like the updates topic).  ``departments`` keys the
    requests topic by department: one partition for each of departments 0
    to ``departments - 1`` behind partition 0, which takes the requests
    that name none (``department_partition``)."""
    if departments is not None:
        request_partitions = max(request_partitions, int(departments) + 1)
    for name, parts in ((requests_topic, request_partitions),
                        (responses_topic, response_partitions)):
        try:
            transport.num_partitions(name)
        except KeyError:
            transport.create_topic(name, parts)


def department_partition(department: int | None, partitions: int) -> int:
    """The partition of a requests topic keyed by department that takes a
    request naming ``department``: 0 for none, 1 + d for department d.  A
    topic with no such partition is not keyed by this department: refused."""
    if department is None:
        return 0
    if not 0 <= int(department) < partitions - 1:
        raise ValueError(
            f"department {department} has no partition on a requests topic "
            f"of {partitions}: a topic keyed by department holds one for "
            "each department and one for none "
            "(ensure_serve_topics(departments=))")
    return 1 + int(department)


def _waiting_since(record) -> float:
    """When ``record`` reached the head of the line, on
    ``time.perf_counter``: when the log appended it where the log says
    (``Record.appended``), else now, when this server first sees it."""
    return record.appended or time.perf_counter()


class RecommendServer:
    """Drain score requests from the log, answer in coalesced batches."""

    def __init__(
        self,
        engine,
        transport,
        *,
        requests_topic: str = REQUESTS_TOPIC,
        responses_topic: str = RESPONSES_TOPIC,
        max_batch: int = 256,
        poll_wait_s: float = 0.002,
        metrics=None,
        metrics_port: int | None = None,
        partitions=None,
        admission=None,
        staleness_fn=None,
        labels: dict | None = None,
        session=None,
        session_factory=None,
    ) -> None:
        from cfk_tpu.utils.metrics import Metrics

        self.engine = engine
        self.transport = transport
        self.requests_topic = requests_topic
        self.responses_topic = responses_topic
        self.max_batch = int(max_batch)
        self.poll_wait_s = poll_wait_s
        self.metrics = metrics if metrics is not None else Metrics()
        # Fleet seams (ISSUE 18): ``partitions`` restricts this server to
        # its OWN request partitions (a fleet replica owns partition i of
        # N; standalone servers keep draining them all); ``admission``
        # sheds polled backlog beyond the controller's queue depth with
        # retriable rejections; ``staleness_fn`` supplies the per-response
        # staleness bound (the replica's unapplied delta backlog).
        self.admission = admission
        self._staleness_fn = staleness_fn
        # The stream this server folds in between its polls (a
        # ``StreamSession``, normally on this engine's table), or None
        # (no stream, or its successor is being brought up).
        # ``session_factory()`` returns a new session on the same store,
        # log and engine: given one, the server replaces a session whose
        # ``pump`` raised; with none, that exception ends the loop.
        self.session = session
        self._session_factory = session_factory
        if session is not None:
            # This thread shares the interpreter with the store's writer
            # threads and, after a kill, with the successor's resume: work
            # that is many short file operations, each of which gives the
            # interpreter's lock away and asks for it back.  At the default
            # 5 ms a turn a commit unit's ~20 such turns took 100 ms under a
            # busy serving thread (PERF.md section 6, PR 39); the threads
            # beside the loop are asked for theirs after 1 ms.
            sys.setswitchinterval(min(sys.getswitchinterval(), 1e-3))
        self._recovering: _Recovery | None = None
        # one record a recovery, in order (``_Recovery.report``): the
        # numbers of the ``stream/recover`` spans, for a caller with no
        # tracer
        self.recoveries: list[dict] = []
        nparts = transport.num_partitions(requests_topic)
        own = (range(nparts) if partitions is None
               else [int(p) for p in partitions])
        self._cursors = {p: 0 for p in own}
        # partition -> when the record at its cursor was appended (where
        # the log says; else when this server first saw it there), for the
        # partitions that hold one: ``_next_partition``
        self._heads: dict[int, float] = {}
        # Committed cursors move only AFTER a batch's responses are
        # produced and flushed — the failover handoff point: a survivor
        # adopting a dead replica's partition resumes here, re-serving
        # (at-least-once) anything the victim had polled but not yet
        # answered, so no accepted request is ever silently lost.
        self.committed_cursors = dict(self._cursors)
        # One batch deep: the batch handed to the device and not answered
        # yet (``step``), and the ordinal the spans of a step share.
        self._in_flight: _Polled | None = None
        self._steps = 0
        self.requests_served = 0
        self.batches = 0
        self.malformed_requests = 0
        self.shed = 0
        # Live metrics export (ISSUE 14): with a port, this server scrapes
        # — GET /metrics answers the Prometheus text rendering of
        # self.metrics even while batches are in flight (the registry is
        # thread-safe; 0 binds an ephemeral port, read it back from
        # .metrics_server.port).  /readyz reports the ENGINE's readiness
        # (prewarmed + epoch table loaded), distinct from /healthz
        # liveness; ``labels`` ride every sample (per-replica attribution
        # through the PR 16 constant-label seam).
        self.metrics_server = None
        if metrics_port is not None:
            from cfk_tpu.telemetry import MetricsHTTPServer

            self.metrics_server = MetricsHTTPServer(
                self.metrics, port=int(metrics_port), labels=labels,
                ready_fn=lambda: self.ready,
            ).start()

    @property
    def ready(self) -> bool:
        """Readiness = the engine's (prewarmed + table loaded); engines
        without the flag (doubles in tests) read as ready."""
        return bool(getattr(self.engine, "ready", True))

    def adopt_partition(self, partition: int, cursor: int = 0) -> None:
        """Take over a request partition at ``cursor`` (failover: the
        supervisor hands a dead replica's partition to a survivor at the
        victim's COMMITTED cursor)."""
        p = int(partition)
        self._cursors[p] = int(cursor)
        self.committed_cursors[p] = int(cursor)
        self._heads.pop(p, None)

    def close(self) -> None:
        """Release the /metrics endpoint (idempotent)."""
        if self.metrics_server is not None:
            self.metrics_server.stop()
            self.metrics_server = None

    def __enter__(self) -> "RecommendServer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def _poll_requests(self) -> list[ScoreRequest]:
        """One batch: the run of records at the head of ONE partition that
        name the same department (or none), up to ``max_batch``, in offset
        order.  One batch shares one scan, so it never mixes departments;
        the first record that names another stays in the log, the head of
        the next run.  The partition is the one whose head has waited
        longest (``_next_partition``), so under a backlog no partition
        starves another whatever each holds."""
        p = self._next_partition()
        if p is None:
            return []
        out: list[ScoreRequest] = []
        got = 0
        head = None  # the record the cursor will stand at, where seen
        records = iter(self.transport.consume(
            self.requests_topic, p, self._cursors[p]))
        for rec in records:
            try:
                req = decode_score_request(rec.value)
            except ValueError:
                self.malformed_requests += 1
                self.metrics.incr("serve_malformed_requests")
            else:
                if out and req.department != out[0].department:
                    head = rec
                    break
                out.append(req)
            got += 1  # cursor advances past the frame either way: a
            # malformed frame must be SKIPPED, not re-read forever —
            # re-raising before the cursor moved would wedge every
            # restart on the same poison offset
            if got >= self.max_batch:
                if len(self._cursors) > 1:
                    head = next(records, None)
                break
        self._cursors[p] += got
        self._heads.pop(p, None)
        if head is not None:
            self._heads[p] = _waiting_since(head)
        return out

    def _next_partition(self) -> int | None:
        """The partition to poll: of those that hold a record, the one
        whose head has waited longest: by the time the log appended it
        where the log says (``Record.appended``), else by when this server
        first saw it at the head.  A server of one partition asks nothing
        of the log here."""
        if len(self._cursors) == 1:
            return next(iter(self._cursors))
        best = None
        for p, cursor in self._cursors.items():
            since = self._heads.get(p)
            if since is None:
                if self.transport.end_offset(self.requests_topic,
                                             p) <= cursor:
                    continue
                head = next(iter(self.transport.consume(
                    self.requests_topic, p, cursor)), None)
                if head is None:
                    continue
                since = self._heads[p] = _waiting_since(head)
            if best is None or since < best[0]:
                best = (since, p)
        return None if best is None else best[1]

    def _pending(self) -> int:
        """Requests produced to this server's partitions and not yet
        polled: the backlog that waits for the next batch."""
        return sum(
            self.transport.end_offset(self.requests_topic, p) - cursor
            for p, cursor in self._cursors.items()
        )

    def _stamp(self) -> tuple[int, int]:
        """(epoch, staleness) for this batch's response stamps."""
        epoch = int(getattr(self.engine, "epoch", 0))
        stale = 0
        if self._staleness_fn is not None:
            try:
                stale = int(self._staleness_fn())
            except Exception:
                stale = -1  # unknown beats a silently-wrong 0
        return epoch, stale

    def step(self) -> int:
        """Poll one coalesced batch and hand it to the device, then fetch
        and answer the batch in flight; returns the number of requests
        ANSWERED in this step (0 = nothing answered).

        Whether the batch just polled stays in flight on the device over
        the next step is decided by what the server sees.  With nothing in
        flight and nothing more pending after the poll it is answered in
        this same step, so a lightly loaded server adds no latency.  Under
        a backlog it stays in flight and the older batch is answered: the
        device scores batch n+1 under the fetch, the responses and the next
        poll of batch n, and the first such step answers nothing.  A poll
        that finds nothing answers the batch in flight, so a caller that
        steps until every answer is in terminates.

        Requests shed by admission control are answered too — with an
        explicit RETRIABLE rejection, never a silent drop — and, like the
        out-of-range rows' errors, with their batch: each once, counted in
        the return value once."""
        return self._step(self._poll_requests)

    def drain(self) -> int:
        """Answer the batch in flight, if there is one, without polling
        for another; returns the number of requests answered."""
        return self._step(list)

    def _step(self, poll) -> int:
        in_flight = self._in_flight
        if self._recovering is not None:
            self._supervise()
        if self.session is not None:
            # the micro-batches that are due, then the next request batch;
            # with a scorer in flight the session waits for no fold-in
            # behind it (``StreamSession.pump``)
            try:
                self.session.pump(
                    device_busy=in_flight is not None and in_flight.on_device)
            except Exception as e:
                if self._session_factory is None:
                    raise
                self._session_died(e)
            if self._recovering is not None:
                self._recovering.pumped(self.session)
        # The spans of one step share its ordinal.  A step that neither
        # polls nor answers anything writes no event — an idle server
        # polls every millisecond.
        ordinal = self._steps + 1
        with span("serve/poll", batch=ordinal) as sp:
            malformed = self.malformed_requests
            reqs = poll()
            # what the log holds of this batch: committed once it is answered
            cursors = dict(self._cursors)
            if not reqs and in_flight is None:
                sp.drop()
            elif get_tracer() is not None:
                sp.set(requests=len(reqs),
                       malformed=self.malformed_requests - malformed,
                       pending_after=self._pending())
                if reqs and reqs[0].department is not None:
                    # what waits in each partition of a topic keyed by
                    # department (partition p: department p - 1)
                    sp.set(department=reqs[0].department,
                           pending_by_department={
                               p - 1: self.transport.end_offset(
                                   self.requests_topic, p) - cursor
                               for p, cursor in self._cursors.items() if p})
        # a fuzzed frame can decode into a request whose reply_partition
        # doesn't exist — unanswerable (there is no partition to refuse
        # it on), so it is counted and dropped BEFORE admission rather
        # than letting the produce raise and kill its co-batched
        # neighbors (or consume queue depth a real request needed)
        nresp = self.transport.num_partitions(self.responses_topic)
        routable = []
        for r in reqs:
            if 0 <= r.reply_partition < nresp:
                routable.append(r)
            else:
                self.malformed_requests += 1
                self.metrics.incr("serve_malformed_requests")
        reqs = routable
        if not reqs and in_flight is None:
            return 0
        shed: list[ScoreRequest] = []
        if reqs and self.admission is not None:
            reqs, shed = self.admission.admit(reqs)
        self._steps = ordinal
        # a batch was on the device while this step polled, and stays
        # there while it assembles, uploads and hands over the next
        overlapped = in_flight is not None and in_flight.on_device
        if overlapped:
            self.metrics.incr("serve_batches_overlapped")
        with span("serve/batch", requests=len(reqs), shed=len(shed),
                  batch=ordinal, overlapped=overlapped) as sp:
            polled = (self._validate(reqs, shed, cursors)
                      if reqs or shed else None)
            # a batch that names a department is scored over its range
            # alone; one that names none takes no such argument
            ranged = {}
            if polled is not None and polled.department is not None:
                ranged["department"] = polled.department
                lo, hi = self.engine.department_range(polled.department)
                sp.set(department=polled.department, range_rows=hi - lo,
                       range_tiles=range_tiles(lo, hi, self.engine.tile_m))
                self.metrics.incr(
                    f"serve_dept_batches_{polled.department}")
            if in_flight is None and self._pending() == 0:
                # straight through: engine.topk is both halves of the
                # batch back to back, under the spans of one
                stamp: dict = {}
                answer = (self.engine.topk(polled.rows, polled.k, stamp=stamp,
                                           **ranged)
                          if polled.valid else None)
                polled.ordinal = stamp.get("ordinal", polled.ordinal)
                return self._respond(polled, answer)
            try:
                if polled is not None and polled.valid:
                    # the assemble, seen_tiles and upload spans: the
                    # host's side of the polled batch's timeline
                    polled.handle = self.engine.stage(
                        polled.rows, polled.k, **ranged)
                    polled.epoch = polled.handle.epoch
                    polled.ordinal = polled.handle.ordinal
                # one serve/batch/compute: the polled batch's dispatch,
                # the answered batch's fetch and counters
                answer = compute(
                    None if polled is None else polled.handle,
                    None if in_flight is None else in_flight.handle)
            except BaseException:
                # One half failed; the other's batch is not lost with it.
                # A failed hand-over leaves the older batch in flight.  A
                # failed fetch drops its batch and leaves the one just
                # handed over in flight.  Whatever goes unanswered stays
                # uncommitted, for an heir to re-serve.
                if in_flight is not None and in_flight.fetch_failed:
                    self._in_flight = polled
                raise
            self._in_flight = polled
            if in_flight is None:
                return 0
            return self._respond(in_flight, answer)

    # -- the stream task's supervisor ---------------------------------------

    def _session_died(self, error: Exception) -> None:
        """``pump`` raised: the session is abandoned here and now, its
        successor is brought up beside the loop."""
        dead, self.session = self.session, None
        if self._recovering is not None:  # the successor died catching up
            self.recoveries.append(self._recovering.report())
        rec = self._recovering = _Recovery(dead, error)
        self.metrics.incr("serve_session_deaths")
        record_event("serve", "session_died",
                     error=f"{type(error).__name__}: {error}",
                     stream_step=rec.dropped["stream_step"])
        rec.thread = threading.Thread(
            target=rec.bring_up, args=(self._session_factory,),
            name="cfk-stream-recover", daemon=True)
        rec.thread.start()

    def _supervise(self) -> None:
        """Between two steps of a recovery: adopt the successor once it is
        up, close the record once it has caught up.  A factory that raised
        ends the loop with its error: a store that cannot be resumed is not
        survivable."""
        rec = self._recovering
        if self.session is None:
            if rec.thread.is_alive():
                return
            if rec.error is not None:
                self._recovering = None
                raise rec.error
            self.session = rec.adopt()
        elif rec.done:
            self.recoveries.append(rec.report())
            self._recovering = None

    def _validate(self, reqs, shed, cursors) -> "_Polled":
        """One polled batch as the engine that serves now sees it; the
        record keeps all that its answer will need."""
        t0 = time.perf_counter()
        epoch, staleness = self._stamp()
        engine = self.engine
        # Refuse out-of-range rows per REQUEST (an error response),
        # never per batch — one bad query must not poison its
        # co-batched neighbors.
        with span("serve/batch/validate", requests=len(reqs)):
            valid: list[ScoreRequest] = []
            errors: list[tuple[ScoreRequest, str]] = []
            # the most a request may ask for: the catalogue's items, or
            # those of the department the batch's requests name (all the
            # same one: ``_poll_requests``); a department this engine does
            # not hold is refused in words, never answered over the whole
            department = reqs[0].department if reqs else None
            most, unknown = engine.num_movies, ""
            if department is not None:
                try:
                    lo, hi = engine.department_range(department)
                    most = hi - lo
                except (AttributeError, ValueError) as e:
                    unknown = str(e) or f"no department {department}"
            for r in reqs:
                if unknown:
                    errors.append((r, unknown))
                elif 0 <= r.user < engine.num_users and 1 <= r.k <= most:
                    valid.append(r)
                else:
                    errors.append((r, (
                        f"user row {r.user} out of range "
                        f"[0, {engine.num_users}) or k {r.k} "
                        f"outside [1, {most}]")))
            if not valid:
                department = None  # nothing to scan
        rows = k_pad = None
        if valid:
            k_pad = _pow2_ceil(
                max(r.k for r in valid),
                min(8, engine.num_movies),
            )
            k_pad = min(k_pad, engine.num_movies)
            rows = np.asarray([r.user for r in valid], np.int64)
        return _Polled(valid, errors, shed, rows, k_pad, epoch, staleness,
                       cursors, t0, department=department)

    def _respond(self, batch: "_Polled", answer) -> int:
        """Produce and flush ``batch``'s responses, then commit its read
        cursors; returns the number of requests answered."""
        epoch, staleness = batch.epoch, batch.staleness
        ordinal = batch.ordinal
        # respond: response objects, encode, produce, flush
        with span("serve/batch/respond") as sp:
            responses: list[tuple[int, ScoreResponse]] = []
            if batch.valid:
                scores, ids = answer
            for i, r in enumerate(batch.valid):
                responses.append((r.reply_partition, ScoreResponse(
                    req_id=r.req_id,
                    movie_rows=ids[i, : r.k],
                    scores=scores[i, : r.k],
                    epoch=epoch, staleness=staleness, ordinal=ordinal,
                )))
            for r, text in batch.errors:
                responses.append((r.reply_partition, ScoreResponse(
                    req_id=r.req_id,
                    movie_rows=np.zeros(0, np.int32),
                    scores=np.zeros(0, np.float32),
                    error=text, epoch=epoch, staleness=staleness,
                )))
            for r in batch.shed:
                # Explicit retriable rejection: the client backs off
                # and re-sends; the request is ANSWERED, not dropped.
                responses.append((r.reply_partition, ScoreResponse(
                    req_id=r.req_id,
                    movie_rows=np.zeros(0, np.int32),
                    scores=np.zeros(0, np.float32),
                    error="overloaded: admission queue depth exceeded",
                    retriable=True, epoch=epoch, staleness=staleness,
                )))
            produced = 0
            for part, resp in responses:
                value = encode_score_response(resp)
                produced += len(value)
                self.transport.produce(
                    self.responses_topic,
                    key=int(resp.req_id % (1 << 31)),
                    value=value, partition=part,
                )
            flush = getattr(self.transport, "flush", None)
            if flush is not None:
                flush()
            sp.set(responses=len(responses), bytes=produced)
        # Responses durable → commit the read cursors as they stood after
        # THIS batch's poll (the failover handoff), never ``_cursors`` as
        # they stand: those already cover the batch in flight.
        self.committed_cursors.update(batch.cursors)
        served, shed = len(batch.valid) + len(batch.errors), len(batch.shed)
        self.requests_served += served
        self.batches += 1
        if shed:
            self.shed += shed
            self.metrics.incr("serve_shed", shed)
            record_event("serve", "shed", requests=shed, served=served)
        self.metrics.incr("serve_requests", served)
        self.metrics.incr("serve_batches")
        # Bounded-reservoir latency distributions (ISSUE 14): per-batch
        # wall, from its validation to its flush, and coalesced size —
        # the /metrics summary quantiles.
        self.metrics.observe("serve_batch_ms",
                             (time.perf_counter() - batch.t0) * 1e3)
        self.metrics.observe("serve_batch_size", served)
        record_event("serve", "batch", requests=served,
                     batch=self.batches)
        return served + shed

    def serve_forever(self, *, max_requests: int | None = None,
                      idle_timeout_s: float | None = None,
                      stop=None) -> int:
        """Poll-and-serve loop; returns requests served.  Stops when
        ``stop()`` goes true, after ``max_requests``, or once the topic
        has been idle ``idle_timeout_s`` (None = keep polling), and
        answers the batch in flight before it returns."""
        served = 0
        idle_since = time.monotonic()
        while True:
            if stop is not None and stop():
                break
            if max_requests is not None and served >= max_requests:
                break
            got = self.step()
            if got:
                served += got
                idle_since = time.monotonic()
                continue
            if (idle_timeout_s is not None
                    and time.monotonic() - idle_since >= idle_timeout_s):
                break
            time.sleep(self.poll_wait_s)
        return served + self.drain()


class _Recovery:
    """One replacement of a stream session, from the exception out of its
    ``pump`` to its successor having caught up with the log.

    Spans (the tracer's; the same numbers are in ``report()``):
    ``stream/recover`` from the exception to the successor's first
    publication of a unit of its own (to its adoption where the log holds
    nothing for it), with ``units``, ``snapshot_bytes``, ``unit_bytes``
    read by the resume, ``replayed_records`` (consumed by the dead session
    and not durable: read from the log again), ``lost_units`` (handed to
    its writer, or committed in memory, and not renamed at the kill),
    ``in_flight_batches`` dropped; the resume's own
    ``stream/recover/restore``, ``.../state``, ``.../republish`` on the
    recovery thread (``StreamSession._try_resume``); ``stream/recover/catchup``
    from that first publication to a backlog under one micro-batch
    (``records``, ``micro_batches``)."""

    def __init__(self, dead, error: Exception) -> None:
        self.t0_ns = time.perf_counter_ns()
        self.cause = f"{type(error).__name__}: {error}"
        self.dropped = dead.abandon()
        # the dead session's store alone: the session itself (its overlay,
        # its solved rows) is let go here, not held beside its successor's
        self._dead_store = dead.manager
        self.thread: threading.Thread | None = None
        self.successor = None
        self.error: BaseException | None = None
        self.up_s = self.publishing_s = self.caught_up_s = None
        self.resume_s = None  # the factory's call alone
        self.lost_units = self.replayed_records = 0
        self.catchup: dict = {}
        self.done = False

    def _since_s(self) -> float:
        return (time.perf_counter_ns() - self.t0_ns) * 1e-9

    def bring_up(self, factory) -> None:
        """On the recovery thread: wait out the write the dead session's
        writer had under way (it lands whole or not at all, and must not
        land over a step of the successor), then resume from the store."""
        from cfk_tpu.resilience.loop import drain_checkpoints

        try:
            try:
                drain_checkpoints(self._dead_store)
            except Exception:  # the dead writer's error died with it
                pass
            t0 = time.perf_counter()
            self.successor = factory()
            self.resume_s = time.perf_counter() - t0
        except BaseException as e:
            self.error = e
        self.up_s = self._since_s()

    def adopt(self):
        """On the serving thread, once the successor is up: what the dead
        session had and the store had not."""
        new, dropped = self.successor, self.dropped
        self.lost_units = dropped["stream_step"] - new.stream_step
        self.replayed_records = sum(
            dropped["cursors"][p] - new.consumer.cursors.get(p, 0)
            for p in dropped["cursors"])
        self.step0 = new.stream_step
        self.cursor0 = sum(new.consumer.cursors.values())
        new.metrics.incr("session_restarts")
        new.metrics.incr("replayed_records", self.replayed_records)
        new.metrics.incr("units_discarded", self.lost_units)
        record_event("serve", "session_replaced", stream_step=self.step0,
                     lost_units=self.lost_units,
                     replayed_records=self.replayed_records)
        return new

    def pumped(self, session) -> None:
        """After each ``pump`` of the successor: has it published a unit of
        its own yet, has it caught up."""
        if session is None:
            return
        tracer = get_tracer()
        published = session.published_step
        if self.publishing_s is None and (
                published > self.step0
                or not (session.backlog() or session.in_flight)):
            self.publishing_s = self._since_s()
            if tracer is not None:
                tracer.complete(
                    "stream/recover", self.t0_ns, cause=self.cause,
                    lost_units=self.lost_units,
                    replayed_records=self.replayed_records,
                    in_flight_batches=self.dropped["in_flight_batches"],
                    **{k: session.resume_stats.get(k, 0) for k in (
                        "units", "snapshot_bytes", "unit_bytes")})
            self.t1_ns = time.perf_counter_ns()
        if (self.publishing_s is not None
                and session.backlog() < session.stream.batch_records):
            self.caught_up_s = self._since_s()
            self.catchup = {
                "records": sum(session.consumer.cursors.values())
                - self.cursor0,
                "micro_batches": session.stream_step - self.step0}
            if tracer is not None:
                tracer.complete("stream/recover/catchup", self.t1_ns,
                                **self.catchup)
            self.done = True

    def report(self) -> dict:
        stats = getattr(self.successor, "resume_stats", {})
        return {
            "cause": self.cause,
            "killed_at": self.t0_ns * 1e-9,  # on ``time.perf_counter``
            "up_s": self.up_s, "publishing_s": self.publishing_s,
            "caught_up_s": self.caught_up_s,
            "lost_units": self.lost_units,
            "replayed_records": self.replayed_records,
            "in_flight_batches": self.dropped["in_flight_batches"],
            "resume_s": self.resume_s,
            **{k: stats.get(k, 0) for k in (
                "units", "snapshot_bytes", "unit_bytes", "republished")},
            **{k: stats.get(k) for k in (
                "restore_s", "state_s", "republish_s")},
            **{"catchup_" + k: v for k, v in self.catchup.items()},
        }


@dataclasses.dataclass
class _Polled:
    """One polled batch, from its poll to its answer."""

    valid: list  # requests the engine scores
    errors: list  # (request, error text): rows or k out of range
    shed: list  # requests admission refused, retriable
    rows: np.ndarray | None  # the valid requests' user rows
    k: int | None  # their padded K
    epoch: int  # of the table it is scored against
    staleness: int  # read when it was polled
    cursors: dict  # the read cursors as they stood after its poll
    t0: float
    handle: TopKBatch | None = None  # staged on the engine, under a backlog
    ordinal: int = 0  # the stream commit its rows and seen lists are as of
    department: int | None = None  # the one its requests name, if any

    @property
    def on_device(self) -> bool:
        return self.handle is not None and self.handle.on_device

    @property
    def fetch_failed(self) -> bool:
        return self.handle is not None and self.handle.failed


class ServeClient:
    """Produce score requests, consume this client's response partition."""

    def __init__(
        self,
        transport,
        *,
        reply_partition: int = 0,
        requests_topic: str = REQUESTS_TOPIC,
        responses_topic: str = RESPONSES_TOPIC,
        route: str = "req",
        metrics=None,
    ) -> None:
        import os

        self.transport = transport
        self.requests_topic = requests_topic
        self.responses_topic = responses_topic
        self.reply_partition = int(reply_partition)
        self._req_parts = transport.num_partitions(requests_topic)
        # Which partition of the requests topic takes a request.  "req":
        # the req_id's spread, for standalone servers, where any partition
        # reaches the one server anyway.  "user" (fleet routing, ISSUE 18):
        # user % N (the PureModPartitioner rule) pins every request for a
        # user onto ONE replica's partition, so a user's answers come from
        # a single hot-row overlay.  "department": a topic keyed by
        # department (``ensure_serve_topics(departments=)``), every request
        # on its department's partition (``department_partition``), so that
        # the log hands the server batches of one department.
        if route not in ("req", "user", "department"):
            raise ValueError(
                f"route={route!r}: requests are routed by 'req', 'user' or "
                "'department'")
        self.route = route
        self.metrics = metrics
        # req_ids start at a random 40-bit base: the response partition is
        # supposed to be one-per-client, but if two clients DO share one
        # (misconfiguration), colliding id sequences would silently
        # mis-attribute answers — a random base makes that astronomically
        # unlikely instead of guaranteed.
        self._next_req = int.from_bytes(os.urandom(5), "big") << 16
        # the send under way, opened by the first request since the last
        # flush: (the tracer's clock then, 0 with tracing off; its req_id)
        self._send: tuple[int, int] | None = None
        self._cursor = transport.end_offset(responses_topic, reply_partition)
        self.malformed_responses = 0
        self.retries = 0
        self.rejections = 0

    def request(self, user: int, k: int,
                department: int | None = None) -> int:
        """Send one query; returns its req_id (the response's echo key).
        ``department`` restricts the answer to that department's items."""
        req_id = self._next_req
        if self._send is None:
            self._send = (time.perf_counter_ns()
                          if get_tracer() is not None else 0, req_id)
        self._next_req += 1
        if self.route == "department":
            part = department_partition(department, self._req_parts)
        else:
            part = (int(user) if self.route == "user"
                    else req_id) % self._req_parts
        self.transport.produce(
            self.requests_topic,
            key=int(user) % (1 << 31),
            value=encode_score_request(ScoreRequest(
                req_id=req_id, user=int(user), k=int(k),
                reply_partition=self.reply_partition,
                department=None if department is None else int(department),
            )),
            partition=part,
        )
        return req_id

    def flush(self) -> None:
        """Hand every request produced since the last flush to the log.
        One ``serve/client/flush`` span a call, the client's send: from the
        first of those requests to the flush's return (none where nothing
        was sent)."""
        send, self._send = self._send, None
        flush = getattr(self.transport, "flush", None)
        if flush is not None:
            flush()
        tracer = get_tracer()
        if tracer is not None and send is not None and send[0]:
            tracer.complete("serve/client/flush", send[0],
                            requests=self._next_req - send[1])

    def poll_responses(self) -> list[ScoreResponse]:
        """All responses that arrived since the last poll.  A malformed
        frame is counted and skipped with the cursor advanced — the same
        no-wedge rule as the server's request poll.  One
        ``serve/client/poll`` span a call, the client's collect; a poll
        that finds nothing writes no event."""
        with span("serve/client/poll") as sp:
            out = []
            malformed = self.malformed_responses
            recs = list(self.transport.consume(
                self.responses_topic, self.reply_partition, self._cursor))
            for rec in recs:
                try:
                    out.append(decode_score_response(rec.value))
                except ValueError:
                    self.malformed_responses += 1
            self._cursor += len(recs)
            if not recs:
                sp.drop()
            elif get_tracer() is not None:
                sp.set(responses=len(out),
                       bytes=sum(len(rec.value) for rec in recs),
                       malformed=self.malformed_responses - malformed)
        return out

    def ask(self, users, k: int, *, server=None, timeout_s: float = 30.0,
            poll_wait_s: float = 0.002, retries: int = 3,
            backoff_base: float = 0.02, rng=None,
            sleep=time.sleep,
            department: int | None = None) -> dict[int, ScoreResponse]:
        """Blocking convenience: send, then poll until every response is
        back — driving ``server.step()`` inline when one is given (the
        single-threaded test mode; with a live server thread/process pass
        None).  Returns {req_id: response} keyed by the FIRST-attempt
        req_ids (stable for callers even when a retry re-sent a query
        under a fresh id).

        Resilience (ISSUE 18): instead of one hard raise at the deadline,
        the poll window splits across ``retries + 1`` attempts with
        exponential backoff + jitter between them (``resilience.retry``
        schedule; ``rng``/``sleep`` injectable so tests assert without
        waiting).  A RETRIABLE rejection (admission-control shed) and a
        response that never arrived (dead replica mid-failover) are both
        re-sent; permanent errors are final answers.  The final failure
        is still a TimeoutError — bounded, never an infinite loop."""
        from cfk_tpu.resilience.retry import backoff_delays

        self.flush()
        ids = [self.request(int(u), k, department) for u in users]
        self.flush()
        user_of = {rid: int(u) for rid, u in zip(ids, users)}
        alias: dict[int, int] = {}  # re-sent req_id -> original req_id
        got: dict[int, ScoreResponse] = {}
        attempts = max(int(retries), 0) + 1
        window = max(timeout_s / attempts, poll_wait_s)
        delays = backoff_delays(base=backoff_base, rng=rng)

        rejected: set[int] = set()  # orig ids shed THIS attempt

        def drain() -> None:
            for resp in self.poll_responses():
                orig = alias.get(resp.req_id, resp.req_id)
                if orig not in user_of:
                    continue  # stale duplicate from a pre-failover serve
                if resp.retriable:
                    self.rejections += 1
                    rejected.add(orig)
                    if self.metrics is not None:
                        self.metrics.incr("serve_client_rejections")
                    continue  # shed — stays missing, re-sent next attempt
                got.setdefault(orig, resp)

        for attempt in range(attempts):
            deadline = time.monotonic() + window
            rejected.clear()
            while set(user_of) - set(got):
                if server is not None:
                    server.step()
                drain()
                missing_now = set(user_of) - set(got)
                if missing_now:
                    # every straggler already answered "retry later" —
                    # nothing more arrives this attempt, back off now
                    if missing_now <= rejected:
                        break
                    if time.monotonic() > deadline:
                        break
                    if server is None:
                        sleep(poll_wait_s)
            missing = set(user_of) - set(got)
            if not missing:
                return got
            if attempt == attempts - 1:
                break
            sleep(next(delays))
            for orig in sorted(missing):
                new_id = self.request(user_of[orig], k, department)
                alias[new_id] = orig
                self.retries += 1
                if self.metrics is not None:
                    self.metrics.incr("serve_client_retries")
            self.flush()
        raise TimeoutError(
            f"{len(set(user_of) - set(got))} of {len(ids)} responses "
            f"missing after {timeout_s}s ({attempts} attempts, "
            f"{self.rejections} rejections seen)"
        )
