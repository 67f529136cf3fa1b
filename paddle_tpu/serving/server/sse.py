"""One writer for every SSE stream of an HTTP server (README "Threads").

A streaming response's handler thread sends the headers, registers the
stream and its socket here (:meth:`StreamWriter.serve`) and parks. From
then on the stream's events reach the socket from the engine-driver
thread: the gateway gathers what one step produced for every stream into
one batch and hands it over once (:meth:`StreamWriter.write`), and the
batch is written there and then, one non-blocking ``send`` an event. No
thread is woken for a token.

A send that would block leaves the rest of that stream's bytes in the
stream's own buffer, and a selector thread, which the first such send
brings into being, drains it when the socket takes more: a reader that is
behind costs its own stream memory for the events it has not read, and
nobody else anything.

The writer belongs to the HTTP server, not to a gateway: a stream that the
fleet moves to another replica keeps its sink, and that replica's driver
writes to it.
"""
from __future__ import annotations

import json
import selectors
import socket
import threading
import time

#: what :meth:`StreamWriter.close` gives readers that are behind to catch
#: up, where the caller names no timeout of its own
CLOSE_GRACE_S = 5.0


def _chunk(stream_id, model_name, token_id, finish_reason, **extra):
    return {"id": stream_id, "object": "text_completion.chunk",
            "model": model_name,
            "choices": [{"index": 0, "token_id": token_id,
                         "finish_reason": finish_reason}], **extra}


def _frame(obj) -> bytes:
    data = obj if isinstance(obj, str) else json.dumps(obj)
    return f"data: {data}\n\n".encode()


_DONE = _frame("[DONE]")


class _Sink:
    """One streaming response: the socket after its headers, the frames of
    the stream's events, and the bytes a send would have blocked on.
    ``lock`` serialises the threads that may write: the drivers, the
    selector thread, and the handler while it registers."""

    def __init__(self, writer, stream, sock, model_name, prompt_tokens):
        self.writer = writer
        self.stream = stream
        self.sock = sock
        self.model_name = model_name
        self.prompt_tokens = prompt_tokens
        # a token's frame is ``json.dumps`` of its chunk, built once: all
        # that differs from token to token is the integer
        head, _, tail = json.dumps(
            _chunk(stream.id, model_name, 0, None)).rpartition('"token_id": 0')
        self._pre = f'data: {head}"token_id": '.encode()
        self._post = f"{tail}\n\n".encode()
        self.lock = threading.Lock()
        self.buf = bytearray()
        self.tokens = 0
        self.last = False       # the terminal frames have been fed
        self.watched = False    # the selector waits for the socket
        #: the last frame is written, or the client is gone
        self.done = threading.Event()

    def _frames(self, event):
        kind, payload = event
        if kind == "token":
            self.tokens += 1
            return (self._pre + b"%d" % payload + self._post,)
        self.last = True
        if kind == "finish":
            n = self.tokens
            body = _chunk(self.stream.id, self.model_name, None, payload,
                          usage={"prompt_tokens": self.prompt_tokens,
                                 "completion_tokens": n,
                                 "total_tokens": self.prompt_tokens + n})
        else:
            # engine-side failure: a FINAL terminal error event (with
            # finish_reason="error") so the client sees a proper end of
            # stream, never a silently dropped connection
            body = _chunk(self.stream.id, self.model_name, None, "error",
                          error={"message": payload, "type": "server_error"})
        return _frame(body), _DONE

    def feed(self, event):
        """Write one event's frames, each with a send of its own as the
        handler thread did; what the socket does not take now goes to
        ``buf``, behind what is there."""
        with self.lock:
            if self.done.is_set():
                return
            for frame in self._frames(event):
                if not self.buf:
                    frame = frame[self._send(frame):]
                    if self.done.is_set():      # the client is gone
                        return
                self.buf += frame
            if self.buf:
                self.writer._backlogged(self)
            elif self.last:
                self._finish()

    def _send(self, data) -> int:
        """Bytes of ``data`` the socket took (0 where it would block). A
        client that went away cancels its sequence, which frees the slot
        and leaves the rest of the batch untouched."""
        try:
            return self.sock.send(data)
        except (BlockingIOError, InterruptedError):
            return 0
        except OSError:
            self.fail()
            return len(data)

    def drain(self):
        """The selector thread: the socket takes more."""
        with self.lock:
            if self.done.is_set():
                return
            del self.buf[:self._send(self.buf)]
            if not self.buf and not self.done.is_set():
                self.writer._unwatch(self)
                if self.last:
                    self._finish()

    def fail(self):
        """The client is gone (``lock`` held)."""
        self.buf.clear()
        self.stream.cancel()
        self._finish()

    def _finish(self):
        self.writer._forget(self)
        self.done.set()


class StreamWriter:
    """Owns the sockets of an HTTP server's streaming responses."""

    def __init__(self, registry):
        # guards _sinks, _closed, and the selector and its thread, which
        # the first stream to fall behind brings into being
        self._lock = threading.Lock()
        self._sinks = set()
        self._closed = False
        self._sel = self._wake_r = self._wake_w = self._thread = None
        self._m_batches = registry.counter(
            "serving_stream_batches_total",
            "Hand-overs of stream events to the writer of the SSE sockets: "
            "one a step that produced any, one for what a stream held when "
            "its response registered.")
        self._m_events = registry.counter(
            "serving_stream_events_total",
            "Stream events (a token, a finish, an error) written to SSE "
            "sockets; over serving_stream_batches_total, the streaming "
            "rows of a step.")
        self._m_backlogged = registry.counter(
            "serving_stream_backlogged_total",
            "Stream events whose send would have blocked and went to their "
            "stream's buffer, which the selector thread drains: a client "
            "that reads slower than its stream decodes.")
        for m in (self._m_batches, self._m_events, self._m_backlogged):
            m.inc(0)

    # ------------------------------------------------------------ handlers
    def serve(self, stream, sock, model_name, prompt_tokens):
        """A streaming response's handler thread, its headers sent: give
        ``stream`` and ``sock`` to the writer and park until the stream's
        last frame is written or the client is gone."""
        sink = _Sink(self, stream, sock, model_name, prompt_tokens)
        timeout = sock.gettimeout()
        sock.setblocking(False)
        with self._lock:
            self._sinks.add(sink)
        try:
            stream.attach(sink)
            sink.done.wait()
        finally:
            sock.settimeout(timeout)

    # -------------------------------------------------------------- drivers
    def write(self, batch):
        """One hand-over: ``[(sink, event)]`` in the order the events were
        pushed. Called by the thread that pushed them."""
        self._m_batches.inc()
        self._m_events.inc(len(batch))
        for sink, event in batch:
            sink.feed(event)

    # ------------------------------------------------------ selector thread
    def _backlogged(self, sink):
        self._m_backlogged.inc()
        if sink.watched:
            return
        with self._lock:
            closed = self._closed
            if not closed:
                if self._sel is None:
                    self._start_draining()
                sink.watched = True
                self._sel.register(sink.sock, selectors.EVENT_WRITE, sink)
        if closed:
            sink.fail()
        else:
            self._wake()

    def _start_draining(self):
        self._sel = selectors.DefaultSelector()
        # a registration from another thread must reach a selector that
        # is waiting already, whatever system call it waits in
        self._wake_r, self._wake_w = socket.socketpair()
        self._wake_r.setblocking(False)
        self._wake_w.setblocking(False)
        self._sel.register(self._wake_r, selectors.EVENT_READ)
        self._thread = threading.Thread(target=self._run, name="sse-drain",
                                        daemon=True)
        self._thread.start()

    def _unwatch(self, sink):
        if sink.watched:
            sink.watched = False
            with self._lock:
                self._sel.unregister(sink.sock)

    def _forget(self, sink):
        self._unwatch(sink)
        with self._lock:
            self._sinks.discard(sink)

    def _wake(self):
        try:
            self._wake_w.send(b"\0")
        except (BlockingIOError, InterruptedError):
            pass                # it is awake already

    def _run(self):
        while True:
            for key, _ in self._sel.select():
                if key.data is not None:
                    key.data.drain()
                    continue
                try:
                    self._wake_r.recv(4096)
                except (BlockingIOError, InterruptedError):
                    pass
                if self._closed:
                    return

    def close(self, timeout=None):
        """After the drivers have stopped: wait for the streams whose
        readers are behind (``timeout`` seconds, :data:`CLOSE_GRACE_S`
        where None), drop what is left, and stop the selector thread.
        Idempotent."""
        deadline = time.monotonic() + (CLOSE_GRACE_S if timeout is None
                                       else timeout)
        with self._lock:
            if self._closed:
                return
            sinks = list(self._sinks)
        for sink in sinks:
            sink.done.wait(max(deadline - time.monotonic(), 0.0))
        with self._lock:
            self._closed = True
            sinks = list(self._sinks)
        for sink in sinks:
            with sink.lock:
                if not sink.done.is_set():
                    sink.fail()
        if self._thread is not None:
            self._wake()
            self._thread.join(5.0)
            self._sel.close()
            self._wake_r.close()
            self._wake_w.close()
