"""ModelRunner: the fused-dispatch executor of the serving stack.

The bottom layer (ARCHITECTURE.md): owns the ``fused_decode_step``
executables (one per static chunk size), the sampling PRNG stream and the
ONE-``device_get``-per-step invariant.  The runner treats the device state
bundle (:class:`repro.serving.kv_manager.DeviceStepState`) as opaque — it
forwards the pool pytree into the fused step and hands the updated pytree
straight back to the manager, never reading an anchor or a version itself
(the layering contract, lint-enforced by ``tests/test_layering.py``).

``launch``/``collect`` split the step so a data-parallel front end
(``serving/parallel.py``) can dispatch EVERY replica's fused step before
blocking on any result: jax dispatch is asynchronous, so N launched steps
overlap on N devices while the host performs the Nth dispatch — the same
amortization argument as the fused step itself, applied across pools.
``execute`` is the single-replica convenience (launch then collect).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ops import resolve_attn_impl
from .kv_manager import DeviceStepState, KVCacheManager
from .paged_decode import fused_decode_step


class StepResult(NamedTuple):
    """One step's host-side results — the contents of the single
    ``device_get``: per-slot next tokens, OA validity, grant info
    (fresh pages granted, −1 = starved), COW flags, advanced-token
    counts and accepted-draft counts (0 on non-speculative steps), all as
    numpy arrays the scheduler consumes."""

    tokens: np.ndarray
    valid: np.ndarray
    grant_info: np.ndarray
    cow: np.ndarray
    adv: np.ndarray
    n_acc: np.ndarray


class ModelRunner:
    """Executes fused decode/prefill steps against a KV manager's device
    state (module docstring).  Holds everything the dispatch needs that is
    NOT page lifecycle: model params, attention implementation knobs, the
    sampling configuration and the per-step PRNG fold."""

    def __init__(self, cfg, params, *, attn_impl: str | None = None,
                 greedy: bool = True, temperature: float = 1.0,
                 seed: int = 0, pages_per_compute_block: int = 1,
                 mesh=None, device=None):
        self.cfg = cfg
        self.params = params
        # attention follows the platform of the devices this runner drives
        # (the mesh's, else its own device, else the default backend's)
        dev = (mesh.devices.flat[0] if mesh is not None
               else device if device is not None else jax.devices()[0])
        self.attn_impl = resolve_attn_impl(attn_impl, dev.platform)
        self.greedy = greedy
        self.pages_per_compute_block = pages_per_compute_block
        # tensor-parallel serving: a ('data','model') mesh threads through
        # to the fused step as a STATIC arg (sharding constraints + the
        # shard_map'ed pallas dispatch); None = the classic 1-device path
        self.mesh = mesh
        self._temperature = jnp.asarray(temperature, jnp.float32)
        self._base_key = jax.random.PRNGKey(seed)
        # resident device scalar for the C=1 executable, where the budget is
        # clipped to 1 anyway: pure-decode steps must not pay a per-step
        # host->device upload for a value that cannot matter
        self._budget_one = jnp.asarray(1, jnp.int32)
        # resident device booleans for the reclamation policy's per-step
        # validation verdict: a TRACED operand of the fused step (selecting
        # a lax.cond branch at runtime, same executable either way), kept
        # resident so skipping validation never costs a per-step upload
        self._val_true = jnp.asarray(True)
        self._val_false = jnp.asarray(False)
        if mesh is not None:
            # every array entering the fused jit must live on the SAME mesh
            # (committed single-device scalars beside mesh-committed state
            # is a placement error) — pin the resident scalars replicated
            rep = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec())
            (self._temperature, self._base_key, self._budget_one,
             self._val_true, self._val_false) = jax.device_put(
                (self._temperature, self._base_key, self._budget_one,
                 self._val_true, self._val_false), rep)
        self._step_idx = 0

    def launch(self, kvm: KVCacheManager, *, chunk_size: int = 1,
               budget: int = 1, drafts: dict | None = None,
               do_validate: bool = True):
        """Dispatch ONE fused step and immediately install the (possibly
        still in-flight — jax arrays are futures) device state back into
        the manager.  Returns the pending per-slot outputs for
        :meth:`collect`; no host transfer happens here, so a front end can
        launch every replica before collecting any.

        ``drafts`` (slot → draft token list, from
        :meth:`repro.serving.scheduler.Scheduler.plan_chunk`) selects the
        SPECULATIVE executable: the plan is packed into dense
        [B, chunk_size−1] / [B] arrays and rides the dispatch as a
        host→device upload — an upload, never a download, so the
        one-``device_get``-per-step invariant is untouched.

        ``do_validate`` is the reclamation policy's verdict for THIS step
        (``Scheduler.plan_validate``): False elides the fused OA
        validation pass via a resident device boolean — no recompile, no
        transfer, same executable."""
        self._step_idx += 1
        # greedy decode never consumes the key — skip the fold_in dispatches
        key = (self._base_key if self.greedy
               else jax.random.fold_in(self._base_key, self._step_idx))
        st = kvm.step_state()
        speculative = drafts is not None
        if speculative:
            B = kvm.max_batch
            dt = np.zeros((B, max(chunk_size - 1, 1)), np.int32)
            dl = np.zeros((B,), np.int32)
            for slot, toks in drafts.items():
                dl[slot] = len(toks)
                dt[slot, :len(toks)] = toks
            draft_args = (jnp.asarray(dt), jnp.asarray(dl))
        else:
            draft_args = (None, None)
        (kv, pool, bt, snap, lengths, last,
         nxt, valid, grant_info, cow, adv, n_acc) = fused_decode_step(
            self.params, st.kv, st.pool, st.block_tables, st.snapshot,
            st.lengths, st.last_tok, st.active, st.prompt_buf, st.prompt_len,
            key, self._temperature,
            (self._budget_one if chunk_size == 1
             else jnp.asarray(budget, jnp.int32)),
            draft_args[0], draft_args[1],
            self._val_true if do_validate else self._val_false,
            cfg=self.cfg, impl=self.attn_impl, greedy=self.greedy,
            pages_per_compute_block=self.pages_per_compute_block,
            chunk_size=chunk_size, speculative=speculative, mesh=self.mesh)
        kvm.install_state(DeviceStepState(
            kv, pool, bt, snap, lengths, last,
            st.active, st.prompt_buf, st.prompt_len))
        return (nxt, valid, grant_info, cow, adv, n_acc)

    def collect(self, pending) -> StepResult:
        """THE one host transfer of a steady-state step: materialise the
        six per-slot arrays in a single ``device_get``."""
        return StepResult(*jax.device_get(pending))

    def execute(self, kvm: KVCacheManager, *, chunk_size: int = 1,
                budget: int = 1, drafts: dict | None = None,
                do_validate: bool = True) -> StepResult:
        """One full step: launch the fused dispatch, then collect its single
        host transfer (the single-replica path)."""
        return self.collect(self.launch(
            kvm, chunk_size=chunk_size, budget=budget, drafts=drafts,
            do_validate=do_validate))
