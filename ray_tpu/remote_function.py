"""`@remote` functions (reference: python/ray/remote_function.py)."""

from __future__ import annotations

import collections
import functools
import inspect
import sys
from typing import Any, Dict, List, Optional, Union

from ray_tpu._private import worker
from ray_tpu._private.ids import ObjectID, TaskID
from ray_tpu.tenancy import context as _tenancy_ctx
from ray_tpu._private.object_ref import ObjectRef
from ray_tpu._private.runtime_env_packaging import \
    prepare_runtime_env as _prepare_runtime_env
from ray_tpu._private.task_spec import (DEFAULT_TASK_OPTIONS, TaskKind,
                                        TaskSpec, resources_from_options,
                                        validate_options)


_NOTHING = object()         # ``ObjectRefGenerator._pop_taken``: none in hand


class ObjectRefGenerator:
    """Iterator over the streamed returns of a generator task.

    Each `next()` yields an ObjectRef as soon as the producer reports the
    item — before the task finishes (reference: ``_raylet.pyx``
    ObjectRefGenerator, proto ``ReportGeneratorItemReturns``).

    A producer may report several items as one object (a
    ``worker.ChunkRun``), and a reader of values that is behind takes
    every object that waits in one go. A reader still gets one item a
    call, in order: ``next_value`` hands what it took out of ``_taken``;
    ``next`` has the runtime split a run (the slow path).
    """

    def __init__(self, task_id: TaskID):
        import threading

        self._task_id = task_id
        self._index = 0
        # multiple threads may share one generator (fan-out consumers);
        # index claims must be atomic or items are delivered twice, and
        # a claim that errors (timeout/transient RPC) returns to the
        # hole set so ANOTHER consumer re-claims it — exactly-once even
        # when consumers fail interleaved
        self._lock = threading.Lock()
        self._holes: set = set()
        # the values ``next_value`` claimed with an item (what waited
        # beyond it) and has not handed out yet
        self._taken: collections.deque = collections.deque()

    def __getstate__(self):
        return {"_task_id": self._task_id, "_index": self._index,
                "_holes": set(self._holes), "_taken": list(self._taken)}

    def __setstate__(self, d):
        import threading

        self._task_id = d["_task_id"]
        self._index = d["_index"]
        self._holes = set(d.get("_holes", ()))
        self._taken = collections.deque(d.get("_taken", ()))
        self._lock = threading.Lock()

    def __iter__(self) -> "ObjectRefGenerator":
        return self

    def __next__(self) -> ObjectRef:
        return self.next()

    def next(self, timeout: Optional[float] = None) -> ObjectRef:
        """``next(gen)`` with a deadline: raises ``GetTimeoutError``
        after ``timeout`` seconds; the claimed index returns to the
        hole set so a retry (or another consumer) re-claims it. One ref
        an ITEM, also where the item travelled in a run."""
        rt = worker.global_worker()
        value = self._pop_taken()   # claimed by ``next_value``: in hand
        if value is not _NOTHING:
            return rt.put(value)
        item = self._next(rt, timeout)[2]
        return item if isinstance(item, ObjectRef) else rt.run_item_ref(item)

    def next_value(self, timeout: Optional[float] = None) -> Any:
        """``get(gen.next(timeout))``, each under the deadline. A reader
        that is behind waits for ONE item and takes with it everything
        that was reported beyond it, the rest of the item's run and the
        objects after (one ``next_ref``, one ``take_waiting``, one
        ``get``): their values wait in ``_taken`` and the next calls
        pop them. A sampled item's ``get`` (the wait for the item is
        over by then), or that of a take that holds sampled items, is
        timed into the stream's account (``Runtime.generator_stats``) where that
        lives in this process, not with a worker's host, and is the span
        ``serve.stream.consume`` where the process has loaded jax (never
        imported for a span's sake)."""
        value = self._pop_taken()
        if value is not _NOTHING:
            return value
        rt = worker.global_worker()
        state, index, item = self._next(rt, timeout)
        if not isinstance(state, worker.GeneratorState):
            return rt.get([item], timeout=timeout)[0]
        in_run = not isinstance(item, ObjectRef)
        refs, more = [item.ref if in_run else item], 0
        if state.produced > index + 1:
            with self._lock:
                # unless another reader of this generator has claimed
                # past this item
                if self._index == index + 1:
                    refs, more = state.take_waiting(index)
                    self._index += more
        sampled = worker.sampled_items(index, 1 + more)
        if not sampled:
            values = rt.get(refs, timeout=timeout)
        else:
            jax = sys.modules.get("jax")
            span = jax and jax.profiler.TraceAnnotation(
                "serve.stream.consume", task=self._task_id.hex(),
                index=worker.first_sampled(index))
            with worker.SampledItem(state, span, sampled):
                values = rt.get(refs, timeout=timeout)
        taken = (values[0][item.offset:item.offset + 1 + more] if in_run
                 else values[:1])
        for value in values[1:]:
            if isinstance(value, worker.ChunkRun):
                taken.extend(value)
            else:
                taken.append(value)
        self._taken.extend(taken[1:])
        return taken[0]

    def _pop_taken(self) -> Any:
        if self._taken:
            try:
                return self._taken.popleft()
            except IndexError:          # another reader took the last
                pass
        return _NOTHING

    def _next(self, rt, timeout: Optional[float]):
        """Claim an index and wait for its item: ``(state, index, ref)``."""
        state = rt.generator_state(self._task_id)
        with self._lock:
            if self._holes:
                index = min(self._holes)
                self._holes.discard(index)
            else:
                index = self._index
                self._index += 1
        try:
            return state, index, state.next_ref(index, timeout=timeout)
        except BaseException:
            with self._lock:
                self._holes.add(index)
            raise

    def __aiter__(self):
        return self

    async def __anext__(self):
        import asyncio
        loop = asyncio.get_event_loop()
        try:
            return await loop.run_in_executor(None, self.__next__)
        except StopIteration:
            raise StopAsyncIteration

    def completed(self) -> bool:
        rt = worker.global_worker()
        return rt.generator_state(self._task_id).finished


class RemoteFunction:
    def __init__(self, func, default_options: Dict[str, Any]):
        self._function = func
        merged = dict(DEFAULT_TASK_OPTIONS)
        merged.update(default_options)
        self._default_options = validate_options(merged, for_actor=False)
        functools.update_wrapper(self, func)

    def __call__(self, *args, **kwargs):
        raise TypeError(
            f"remote function {self._function.__name__} cannot be called "
            f"directly; use {self._function.__name__}.remote()")

    def options(self, **options) -> "_OptionsWrapper":
        merged = dict(self._default_options)
        merged.update(options)
        validate_options(merged, for_actor=False)
        return _OptionsWrapper(self, merged)

    def remote(self, *args, **kwargs):
        return self._remote(args, kwargs, self._default_options)

    def bind(self, *args, **kwargs):
        """Build a DAG node (reference: dag/function_node.py)."""
        from ray_tpu.dag.node import FunctionNode
        return FunctionNode(self, args, kwargs)

    @property
    def _function_name(self) -> str:
        return getattr(self._function, "__name__", "fn")

    def _remote(self, args, kwargs, options) -> Union[ObjectRef,
                                                      List[ObjectRef],
                                                      ObjectRefGenerator]:
        rt = worker.global_worker()
        num_returns = options.get("num_returns", 1)
        if (num_returns == 1
                and inspect.isgeneratorfunction(self._function)):
            num_returns = "streaming"
        n_ids = 1 if not isinstance(num_returns, int) else max(num_returns, 1)
        task_id = TaskID.from_random()
        spec = TaskSpec(
            task_id=task_id,
            kind=TaskKind.NORMAL,
            name=options.get("name") or self._function.__qualname__,
            func=self._function,
            args=tuple(args),
            kwargs=dict(kwargs),
            resources=resources_from_options(options),
            num_returns=num_returns,
            return_ids=[ObjectID.from_random() for _ in range(n_ids)],
            max_retries=options.get("max_retries", 3),
            retry_exceptions=options.get("retry_exceptions", False),
            runtime_env=_prepare_runtime_env(
                options.get("runtime_env")),
            scheduling_strategy=worker.capture_parent_pg_strategy(
                options.get("scheduling_strategy", "DEFAULT")),
            job_id=_tenancy_ctx.current_job_id(rt),
            backpressure_num_objects=options.get(
                "_generator_backpressure_num_objects", -1),
            label_selector=options.get("label_selector"),
            in_process=bool(options.get("_in_process")),
        )
        refs = rt.submit_task(spec)
        if num_returns == "streaming":
            return ObjectRefGenerator(task_id)
        if isinstance(num_returns, int) and num_returns != 1:
            return refs if num_returns > 0 else None
        return refs[0]


class _OptionsWrapper:
    def __init__(self, remote_fn: RemoteFunction, options: Dict[str, Any]):
        self._remote_fn = remote_fn
        self._options = options

    def remote(self, *args, **kwargs):
        return self._remote_fn._remote(args, kwargs, self._options)
