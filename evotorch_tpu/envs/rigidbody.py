"""Maximal-coordinates rigid-body dynamics engine (pure JAX, TPU-first).

This is the substrate for the Humanoid-class flagship workloads: a small
articulated-body simulator in *maximal coordinates* — every body carries its
full 13-dim state (position, quaternion, linear and angular velocity), joints
are stiff spring-damper constraints, and ground contact is a penalty model
with clamped Coulomb-style friction. That formulation (the one Brax v1's
"spring" backend demonstrated for exactly these locomotion tasks) is chosen
deliberately over generalized coordinates: every stage is a fixed-shape
stacked-array computation (gather over joint endpoints, scatter-add of forces,
elementwise integration) with no per-body recursion, so a whole population of
environments vectorizes to ``(popsize, n_bodies, ...)`` arrays with plain
``jax.vmap`` and runs as one fused XLA program.

Parity note: the reference has no simulator of its own — it reaches Brax
through a torch<->jax dlpack bridge (``/root/reference/src/evotorch/
neuroevolution/net/vecrl.py:1366-1490``, ``VectorEnvFromBrax``). Here the
simulator is native to the framework, so the entire population x env x time
loop stays inside one jitted program (``net/vecrl.py:run_vectorized_rollout``).

Conventions
-----------
- Quaternions are ``(w, x, y, z)``.
- Model reference pose: all body frames axis-aligned with the world (identity
  quaternions), origins at each body's center of mass. Joint anchors and axes
  are given in those body frames; relative joint rotation is therefore
  identity in the reference pose.
- Ground is the plane ``z = 0``; gravity points along ``-z``.
"""

from __future__ import annotations

from typing import List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

__all__ = [
    "BodyState",
    "System",
    "SystemBuilder",
    "quat_mul",
    "quat_conj",
    "quat_rotate",
    "quat_rotate_inv",
    "quat_to_rotvec",
    "quat_integrate",
    "physics_substep",
    "physics_step",
    "physics_step_batched",
    "joint_angles",
    "joint_velocities",
    "joint_angles_batched",
    "joint_velocities_batched",
    "sphere_penetrations",
    "sphere_penetrations_batched",
    "capsule_inertia",
    "sphere_inertia",
]


# ---------------------------------------------------------------------------
# Quaternion kernels
# ---------------------------------------------------------------------------


def quat_mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Hamilton product ``a * b`` over the last axis (``(..., 4)``)."""
    aw, ax, ay, az = a[..., 0], a[..., 1], a[..., 2], a[..., 3]
    bw, bx, by, bz = b[..., 0], b[..., 1], b[..., 2], b[..., 3]
    return jnp.stack(
        [
            aw * bw - ax * bx - ay * by - az * bz,
            aw * bx + ax * bw + ay * bz - az * by,
            aw * by - ax * bz + ay * bw + az * bx,
            aw * bz + ax * by - ay * bx + az * bw,
        ],
        axis=-1,
    )


def quat_conj(q: jnp.ndarray) -> jnp.ndarray:
    return q * jnp.asarray([1.0, -1.0, -1.0, -1.0], dtype=q.dtype)


def quat_rotate(q: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """Rotate vector(s) ``v`` by quaternion(s) ``q`` (broadcast over leading
    axes). Uses the 15-mul expansion rather than two Hamilton products."""
    qw = q[..., :1]
    qv = q[..., 1:]
    t = 2.0 * jnp.cross(qv, v)
    return v + qw * t + jnp.cross(qv, t)


def quat_rotate_inv(q: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    return quat_rotate(quat_conj(q), v)


def quat_to_rotvec(q: jnp.ndarray) -> jnp.ndarray:
    """Log map: quaternion -> axis-angle vector (``(..., 3)``), taking the
    shortest arc. Safe at identity (series limit ``2 * xyz``)."""
    q = jnp.where(q[..., :1] < 0.0, -q, q)  # shortest rotation
    w = q[..., 0]
    xyz = q[..., 1:]
    s = jnp.linalg.norm(xyz, axis=-1)
    angle = 2.0 * jnp.arctan2(s, w)
    # angle/s -> 2/w as s -> 0; keep the division finite everywhere
    scale = jnp.where(s < 1e-7, 2.0, angle / jnp.maximum(s, 1e-12))
    return xyz * scale[..., None]


def quat_integrate(q: jnp.ndarray, omega_world: jnp.ndarray, h) -> jnp.ndarray:
    """First-order quaternion update from a world-frame angular velocity."""
    zero = jnp.zeros_like(omega_world[..., :1])
    omega_q = jnp.concatenate([zero, omega_world], axis=-1)
    q_new = q + 0.5 * h * quat_mul(omega_q, q)
    return q_new / jnp.linalg.norm(q_new, axis=-1, keepdims=True)


# ---------------------------------------------------------------------------
# System description + state
# ---------------------------------------------------------------------------


class BodyState(NamedTuple):
    """Dynamic state of all bodies: stacked ``(n_bodies, ...)`` arrays."""

    pos: jnp.ndarray  # (nb, 3) world COM positions
    quat: jnp.ndarray  # (nb, 4) world orientations (w, x, y, z)
    vel: jnp.ndarray  # (nb, 3) world linear velocities
    ang: jnp.ndarray  # (nb, 3) world angular velocities


class System(NamedTuple):
    """Static model description. Arrays here are constants closed over by the
    jitted step, not traced state."""

    # bodies
    mass: jnp.ndarray  # (nb,)
    inertia: jnp.ndarray  # (nb, 3) diagonal body-frame inertia
    # joints
    joint_parent: np.ndarray  # (nj,) int — static gather indices
    joint_child: np.ndarray  # (nj,) int
    anchor_p: jnp.ndarray  # (nj, 3) anchor in parent body frame
    anchor_c: jnp.ndarray  # (nj, 3) anchor in child body frame
    axes: jnp.ndarray  # (nj, 3, 3) joint axes (rows) in parent body frame
    free: jnp.ndarray  # (nj, 3) 1.0 where the axis is a free DOF
    limit_lo: jnp.ndarray  # (nj, 3) lower joint limit per axis (rad)
    limit_hi: jnp.ndarray  # (nj, 3)
    gear: jnp.ndarray  # (nj, 3) actuator torque limit per free axis
    act_index: np.ndarray  # (nj, 3) int — index into the action vector,
    #                         ``num_act`` for unactuated axes (see step)
    num_act: int
    # actuation mode: "torque" (action scales gear directly, MuJoCo-style) or
    # "position" (action maps to a target joint angle inside the limit range;
    # a PD servo with gains act_kp/act_kd tracks it, torque-clipped at gear)
    act_mode: str
    act_kp: jnp.ndarray  # (nj, 3)
    act_kd: jnp.ndarray  # (nj, 3)
    # colliders (spheres vs. ground plane z=0)
    sph_body: np.ndarray  # (ns,) int
    sph_offset: jnp.ndarray  # (ns, 3) in body frame
    sph_radius: jnp.ndarray  # (ns,)
    # per-joint constraint gains. These are derived from target constraint
    # frequencies and the *reduced* mass/inertia of each joint's body pair
    # (k = w^2 m_red, c = 2 zeta w m_red), so light limbs and heavy trunks
    # are equally far from the explicit-integration stability boundary —
    # scalar gains would make arm constraints 1000x stiffer (relative to
    # inertia) than hip constraints.
    pos_k: jnp.ndarray  # (nj,)
    pos_c: jnp.ndarray  # (nj,)
    ang_k: jnp.ndarray  # (nj, 3) per joint axis
    ang_c: jnp.ndarray  # (nj, 3)
    limit_k: jnp.ndarray  # (nj, 3)
    tone_k: jnp.ndarray  # (nj, 3) passive spring toward 0 on free axes
    joint_damping: jnp.ndarray  # (nj, 3) free-axis damping
    # material parameters
    gravity: jnp.ndarray  # (3,)
    contact_k: float
    contact_c: float
    friction_mu: float
    tangent_damping: float
    max_vel: float
    max_ang: float

    @property
    def num_bodies(self) -> int:
        return int(self.mass.shape[0])

    @property
    def num_joints(self) -> int:
        return int(self.anchor_p.shape[0])


# ---------------------------------------------------------------------------
# Dynamics — population-minor ("batch-trailing") formulation
# ---------------------------------------------------------------------------
#
# TPU vector registers are (8 sublanes x 128 lanes) tiles over the two
# minor-most axes. Arrays shaped (popsize, nb, 3) — what `vmap` over a
# single-env step produces — put 3 elements in the 128-lane axis: ~2% lane
# utilization, and the rollout loop carry materializes that padding every
# substep. The engine therefore computes natively on *batch-trailing* arrays
# (nb, 3, B): the population axis fills the lanes, the component axis sits in
# sublanes, and all body gathers/scatters become static row selections /
# one-hot einsum contractions (dense matmuls). Measured on a v5e, this layout
# is >10x faster than the vmap layout for the same loop-carried arithmetic.
# The single-instance API (`physics_step` etc.) is the B=1 special case, so
# there is exactly one implementation of the dynamics.


def _bcross(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Cross product over the component axis -2 (``(..., 3, B)`` layout)."""
    a0, a1, a2 = a[..., 0, :], a[..., 1, :], a[..., 2, :]
    b0, b1, b2 = b[..., 0, :], b[..., 1, :], b[..., 2, :]
    return jnp.stack(
        (a1 * b2 - a2 * b1, a2 * b0 - a0 * b2, a0 * b1 - a1 * b0), axis=-2
    )


def _bquat_mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    aw, ax, ay, az = a[..., 0, :], a[..., 1, :], a[..., 2, :], a[..., 3, :]
    bw, bx, by, bz = b[..., 0, :], b[..., 1, :], b[..., 2, :], b[..., 3, :]
    return jnp.stack(
        (
            aw * bw - ax * bx - ay * by - az * bz,
            aw * bx + ax * bw + ay * bz - az * by,
            aw * by - ax * bz + ay * bw + az * bx,
            aw * bz + ax * by - ay * bx + az * bw,
        ),
        axis=-2,
    )


def _bquat_conj(q: jnp.ndarray) -> jnp.ndarray:
    return q * jnp.asarray([1.0, -1.0, -1.0, -1.0], dtype=q.dtype)[:, None]


def _bquat_rotate(q: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    qw = q[..., :1, :]
    qv = q[..., 1:, :]
    t = 2.0 * _bcross(qv, v)
    return v + qw * t + _bcross(qv, t)


def _bquat_rotate_inv(q: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    return _bquat_rotate(_bquat_conj(q), v)


def _bquat_to_rotvec(q: jnp.ndarray) -> jnp.ndarray:
    q = jnp.where(q[..., :1, :] < 0.0, -q, q)  # shortest rotation
    w = q[..., 0, :]
    xyz = q[..., 1:, :]
    s = jnp.sqrt(jnp.sum(xyz * xyz, axis=-2))
    angle = 2.0 * jnp.arctan2(s, w)
    scale = jnp.where(s < 1e-7, 2.0, angle / jnp.maximum(s, 1e-12))
    return xyz * scale[..., None, :]


def _bquat_integrate(q: jnp.ndarray, omega_world: jnp.ndarray, h) -> jnp.ndarray:
    zero = jnp.zeros_like(omega_world[..., :1, :])
    omega_q = jnp.concatenate([zero, omega_world], axis=-2)
    q_new = q + 0.5 * h * _bquat_mul(omega_q, q)
    return q_new / jnp.sqrt(jnp.sum(q_new * q_new, axis=-2, keepdims=True))


def _bquat_to_mat(q: jnp.ndarray) -> jnp.ndarray:
    """Rotation matrices ``(..., 3, 3, B)`` from quaternions ``(..., 4, B)``.

    The substep rotates ~8 vectors per body quat (joint anchors, relative
    angular velocities, torques, contact offsets, the body-frame angular
    update): building the matrix once (~20 flops) and applying it at 15
    flops/vector halves the rotation arithmetic vs the 30-flop quat-rotate
    formula — the substep is VPU-flop/fusion bound (the r2b
    arithmetic, ROADMAP S2), so this is a direct attack on the dominant cost."""
    w, x, y, z = q[..., 0, :], q[..., 1, :], q[..., 2, :], q[..., 3, :]
    xx, yy, zz = x * x, y * y, z * z
    xy, xz, yz = x * y, x * z, y * z
    wx, wy, wz = w * x, w * y, w * z
    one = jnp.ones_like(w)
    r0 = jnp.stack((one - 2 * (yy + zz), 2 * (xy - wz), 2 * (xz + wy)), axis=-2)
    r1 = jnp.stack((2 * (xy + wz), one - 2 * (xx + zz), 2 * (yz - wx)), axis=-2)
    r2 = jnp.stack((2 * (xz - wy), 2 * (yz + wx), one - 2 * (xx + yy)), axis=-2)
    return jnp.stack((r0, r1, r2), axis=-3)


def _bmat_rotate(R: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """Apply ``(..., 3, 3, B)`` rotation matrices to ``(..., 3, B)`` vectors."""
    return jnp.sum(R * v[..., None, :, :], axis=-2)


def _bmat_rotate_inv(R: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """Apply the transposed (inverse) rotations."""
    return jnp.sum(R * v[..., :, None, :], axis=-3)


def _one_hot(idx: np.ndarray, n: int, dtype) -> jnp.ndarray:
    """Static selection matrix (len(idx), n); body scatters become matmuls."""
    return jnp.asarray(np.eye(n, dtype=np.float32)[np.asarray(idx)], dtype=dtype)


def _scatter_bodies(hot: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """Accumulate per-joint/per-sphere wrenches ``(nj, 3, B)`` onto bodies
    ``(nb, 3, B)`` via a dense one-hot contraction (TPU scatters serialize;
    a (nb, nj) x (nj, 3B) matmul does not)."""
    return jnp.einsum("jb,jkB->bkB", hot, v)


def _joint_forces_batched(sys: System, st: BodyState, actions: jnp.ndarray, R: jnp.ndarray):
    """Per-joint constraint + limit + actuation wrenches for a whole
    population: state arrays ``(nb, comp, B)``, actions ``(num_act, B)``,
    ``R`` the per-body rotation matrices (built once per substep).
    Returns force/torque accumulators ``(nb, 3, B)``."""
    p, c = sys.joint_parent, sys.joint_child
    pq, cq = st.quat[p], st.quat[c]  # (nj, 4, B) — static row gathers
    Rp, Rc = R[p], R[c]
    pp, cp = st.pos[p], st.pos[c]
    pv, cv = st.vel[p], st.vel[c]
    pw, cw = st.ang[p], st.ang[c]

    # --- positional constraint: pull the two anchor points together
    ra = _bmat_rotate(Rp, sys.anchor_p[:, :, None])  # world lever arms
    rb = _bmat_rotate(Rc, sys.anchor_c[:, :, None])
    err = (cp + rb) - (pp + ra)
    verr = (cv + _bcross(cw, rb)) - (pv + _bcross(pw, ra))
    fj = -sys.pos_k[:, None, None] * err - sys.pos_c[:, None, None] * verr

    nb = st.pos.shape[0]
    dtype = st.pos.dtype
    c_hot = _one_hot(c, nb, dtype)
    p_hot = _one_hot(p, nb, dtype)
    inc = c_hot - p_hot  # force on child, reaction on parent
    f = _scatter_bodies(inc, fj)
    tau = _scatter_bodies(c_hot, _bcross(rb, fj)) - _scatter_bodies(
        p_hot, _bcross(ra, fj)
    )

    # --- angular: relative rotation decomposed onto the joint axes
    q_rel = _bquat_mul(_bquat_conj(pq), cq)
    phi = _bquat_to_rotvec(q_rel)  # (nj, 3, B), parent frame
    w_rel = _bmat_rotate_inv(Rp, cw - pw)

    # components along the (orthonormal) joint axes; since the axes form a
    # complete basis, the whole angular response is expressed per component,
    # which lets every axis carry its own gain (a thigh's inertia about its
    # long axis is ~6x smaller than across it — shared gains would put the
    # twist axis past the explicit-integration stability bound)
    phi_comp = jnp.einsum("jak,jkB->jaB", sys.axes, phi)  # (nj, 3, B)
    w_comp = jnp.einsum("jak,jkB->jaB", sys.axes, w_rel)

    limit_hi = sys.limit_hi[:, :, None]
    limit_lo = sys.limit_lo[:, :, None]
    gear = sys.gear[:, :, None]
    over = jnp.maximum(phi_comp - limit_hi, 0.0)
    under = jnp.maximum(limit_lo - phi_comp, 0.0)
    act = jnp.concatenate(
        [actions, jnp.zeros((1,) + actions.shape[1:], dtype=actions.dtype)]
    )
    drive = act[sys.act_index]  # (nj, 3, B); 0 for unactuated axes
    actuated = (gear > 0.0).astype(dtype)
    if sys.act_mode == "position":
        # action in [-1, 1] maps to a target angle: 0 is the reference pose,
        # +/-1 the joint limits; a torque-clipped PD servo tracks it
        target = jnp.where(drive >= 0.0, drive * limit_hi, -drive * limit_lo)
        pd = sys.act_kp[:, :, None] * (target - phi_comp) - sys.act_kd[:, :, None] * w_comp
        act_torque = actuated * jnp.clip(pd, -gear, gear)
    else:
        act_torque = gear * drive
    free = sys.free[:, :, None]
    locked = 1.0 - free
    comp_torque = locked * (
        -sys.ang_k[:, :, None] * phi_comp - sys.ang_c[:, :, None] * w_comp
    ) + free * (
        sys.limit_k[:, :, None] * (under - over)
        - sys.tone_k[:, :, None] * phi_comp
        - sys.joint_damping[:, :, None] * w_comp
        + act_torque
    )
    tau_j = jnp.einsum("jak,jaB->jkB", sys.axes, comp_torque)

    tau_w = _bmat_rotate(Rp, tau_j)  # parent frame -> world
    tau = tau + _scatter_bodies(inc, tau_w)
    return f, tau


def _contact_forces_batched(sys: System, st: BodyState, R: jnp.ndarray):
    """Sphere-vs-ground penalty contacts with clamped viscous friction,
    population-batched (``(ns, 3, B)`` intermediates)."""
    b = sys.sph_body
    dtype = st.pos.dtype
    r_off = _bmat_rotate(R[b], sys.sph_offset[:, :, None])
    pen = sys.sph_radius[:, None] - (st.pos[b][..., 2, :] + r_off[..., 2, :])
    in_contact = pen > 0.0

    # velocity of the lowest point of each sphere
    e_z = jnp.asarray([0.0, 0.0, 1.0], dtype=dtype)[:, None]
    rel = r_off - sys.sph_radius[:, None, None] * e_z
    vc = st.vel[b] + _bcross(st.ang[b], rel)

    fn = jnp.maximum(sys.contact_k * pen - sys.contact_c * vc[..., 2, :], 0.0)
    fn = jnp.where(in_contact, fn, 0.0)

    vt = vc * jnp.asarray([1.0, 1.0, 0.0], dtype=dtype)[:, None]
    vt_norm = jnp.sqrt(vt[..., 0, :] ** 2 + vt[..., 1, :] ** 2)
    # clamped viscous friction: viscous at small slip, Coulomb cap mu*N above
    ft_mag = jnp.minimum(sys.friction_mu * fn, sys.tangent_damping * vt_norm)
    ft = -vt * (ft_mag / jnp.maximum(vt_norm, 1e-6))[..., None, :]
    fc = ft + fn[..., None, :] * e_z

    nb = st.pos.shape[0]
    s_hot = _one_hot(b, nb, dtype)
    f = _scatter_bodies(s_hot, fc)
    tau = _scatter_bodies(s_hot, _bcross(rel, fc))
    return f, tau


def physics_substep_batched(
    sys: System, st: BodyState, actions: jnp.ndarray, h
) -> BodyState:
    """One semi-implicit Euler substep for a population: ``st`` arrays are
    ``(nb, comp, B)``, ``actions`` ``(num_act, B)``."""
    # per-body rotation matrices, built ONCE and shared by every rotation in
    # the substep (joints, contacts, body-frame angular update)
    R = _bquat_to_mat(st.quat)
    fj, tj = _joint_forces_batched(sys, st, actions, R)
    fc, tc = _contact_forces_batched(sys, st, R)
    mass = sys.mass[:, None, None]
    f = fj + fc + mass * sys.gravity[None, :, None]
    tau = tj + tc

    vel = st.vel + h * f / mass
    # angular update in the body frame, where the inertia tensor is diagonal
    inertia = sys.inertia[:, :, None]
    w_body = _bmat_rotate_inv(R, st.ang)
    tau_body = _bmat_rotate_inv(R, tau)
    w_body = w_body + h * (tau_body - _bcross(w_body, inertia * w_body)) / inertia
    ang = _bmat_rotate(R, w_body)

    # stability clamps: cap velocities so stiff-spring transients cannot blow up
    vel = jnp.clip(vel, -sys.max_vel, sys.max_vel)
    ang = jnp.clip(ang, -sys.max_ang, sys.max_ang)

    pos = st.pos + h * vel
    quat = _bquat_integrate(st.quat, ang, h)
    return BodyState(pos=pos, quat=quat, vel=vel, ang=ang)


def physics_step_batched(
    sys: System, st: BodyState, actions: jnp.ndarray, dt: float, substeps: int
) -> BodyState:
    """One control step = ``substeps`` substeps with the action held. Unrolled
    (``substeps`` is static and small) so XLA can fuse across substeps."""
    h = dt / substeps
    for _ in range(int(substeps)):
        st = physics_substep_batched(sys, st, actions, h)
    return st


# -- single-instance API: the B=1 special case ------------------------------


def _to_batched(st: BodyState) -> BodyState:
    return BodyState(*(x[..., None] for x in st))


def _from_batched(st: BodyState) -> BodyState:
    return BodyState(*(x[..., 0] for x in st))


def physics_substep(sys: System, st: BodyState, actions: jnp.ndarray, h) -> BodyState:
    """One semi-implicit Euler substep for all bodies (single instance)."""
    out = physics_substep_batched(sys, _to_batched(st), actions[..., None], h)
    return _from_batched(out)


def physics_step(
    sys: System, st: BodyState, actions: jnp.ndarray, dt: float, substeps: int
) -> BodyState:
    """One control step = ``substeps`` physics substeps with the action held."""
    out = physics_step_batched(
        sys, _to_batched(st), actions[..., None], dt, substeps
    )
    return _from_batched(out)


# ---------------------------------------------------------------------------
# Measurements (observations)
# ---------------------------------------------------------------------------


def joint_angles_batched(sys: System, st: BodyState) -> jnp.ndarray:
    """Rotation of each joint decomposed onto its axes, ``(nj, 3, B)``."""
    pq = st.quat[sys.joint_parent]
    cq = st.quat[sys.joint_child]
    phi = _bquat_to_rotvec(_bquat_mul(_bquat_conj(pq), cq))
    return jnp.einsum("jak,jkB->jaB", sys.axes, phi)


def joint_velocities_batched(sys: System, st: BodyState) -> jnp.ndarray:
    """Relative angular velocity of each joint on its axes, ``(nj, 3, B)``."""
    p, c = sys.joint_parent, sys.joint_child
    w_rel = _bquat_rotate_inv(st.quat[p], st.ang[c] - st.ang[p])
    return jnp.einsum("jak,jkB->jaB", sys.axes, w_rel)


def sphere_penetrations_batched(sys: System, st: BodyState) -> jnp.ndarray:
    """Ground penetration depth per collider sphere (``(ns, B)``, >= 0)."""
    b = sys.sph_body
    r_off = _bquat_rotate(st.quat[b], sys.sph_offset[:, :, None])
    center_z = st.pos[b][..., 2, :] + r_off[..., 2, :]
    return jnp.maximum(sys.sph_radius[:, None] - center_z, 0.0)


def joint_angles(sys: System, st: BodyState) -> jnp.ndarray:
    """Rotation of each joint decomposed onto its axes, ``(nj, 3)``."""
    return joint_angles_batched(sys, _to_batched(st))[..., 0]


def joint_velocities(sys: System, st: BodyState) -> jnp.ndarray:
    """Relative angular velocity of each joint on its axes, ``(nj, 3)``."""
    return joint_velocities_batched(sys, _to_batched(st))[..., 0]


def sphere_penetrations(sys: System, st: BodyState) -> jnp.ndarray:
    """Ground penetration depth per collider sphere (``(ns,)``, clipped >=0)."""
    return sphere_penetrations_batched(sys, _to_batched(st))[..., 0]


# ---------------------------------------------------------------------------
# Inertia helpers + builder
# ---------------------------------------------------------------------------


def capsule_inertia(mass: float, radius: float, length: float, axis: str) -> np.ndarray:
    """Diagonal inertia of a capsule approximated as a solid cylinder of the
    same total length, aligned with ``axis`` in {'x','y','z'}."""
    i_axis = 0.5 * mass * radius**2
    i_perp = mass * (3.0 * radius**2 + length**2) / 12.0
    diag = {"x": (i_axis, i_perp, i_perp), "y": (i_perp, i_axis, i_perp), "z": (i_perp, i_perp, i_axis)}
    return np.asarray(diag[axis], dtype=np.float64)


def sphere_inertia(mass: float, radius: float) -> np.ndarray:
    i = 0.4 * mass * radius**2
    return np.asarray([i, i, i], dtype=np.float64)


def _orthonormal_axes() -> np.ndarray:
    return np.eye(3, dtype=np.float64)


class SystemBuilder:
    """Incrementally assemble a :class:`System` in the reference pose.

    Bodies are declared with world COM positions (identity orientation);
    joints with world anchor points and world axes — the builder converts
    everything to body frames (trivially, since the reference pose is
    axis-aligned).
    """

    def __init__(
        self,
        *,
        gravity: float = -9.81,
        omega_pos: float = 250.0,
        omega_ang: float = 150.0,
        zeta: float = 1.0,
        limit_gain: float = 4.0,
        tone_ratio: float = 0.1,
        free_damping_ratio: float = 0.1,
        contact_k: float = 20_000.0,
        contact_c: float = 60.0,
        friction_mu: float = 1.0,
        tangent_damping: float = 400.0,
        max_vel: float = 50.0,
        max_ang: float = 40.0,
        act_mode: str = "torque",
        act_kp_ratio: float = 1.0,
        act_kd_ratio: float = 1.0,
    ):
        """``omega_pos``/``omega_ang`` (rad/s) are the target constraint
        frequencies; actual spring constants are scaled per joint by the
        reduced mass/inertia of the connected body pair, keeping every
        constraint at the same distance from the semi-implicit-Euler
        stability boundary (``h * omega < 2``). ``zeta`` is the damping
        ratio; ``limit_gain`` scales limit springs relative to the lock
        spring; ``tone_ratio`` adds a weak passive spring pulling free DOF
        toward the reference pose (muscle tone); ``free_damping_ratio``
        scales free-axis damping relative to the lock damping."""
        if act_mode not in ("torque", "position"):
            raise ValueError(f"act_mode must be 'torque' or 'position', got {act_mode!r}")
        self._params = dict(
            gravity=np.asarray([0.0, 0.0, gravity]),
            omega_pos=omega_pos,
            omega_ang=omega_ang,
            zeta=zeta,
            limit_gain=limit_gain,
            tone_ratio=tone_ratio,
            free_damping_ratio=free_damping_ratio,
            contact_k=contact_k,
            contact_c=contact_c,
            friction_mu=friction_mu,
            tangent_damping=tangent_damping,
            max_vel=max_vel,
            max_ang=max_ang,
            act_mode=act_mode,
            act_kp_ratio=act_kp_ratio,
            act_kd_ratio=act_kd_ratio,
        )
        self._names: List[str] = []
        self._pos: List[np.ndarray] = []
        self._mass: List[float] = []
        self._inertia: List[np.ndarray] = []
        self._joints: List[dict] = []
        self._spheres: List[Tuple[int, np.ndarray, float]] = []

    # -- bodies ------------------------------------------------------------
    def add_body(self, name: str, pos, mass: float, inertia) -> int:
        idx = len(self._names)
        self._names.append(name)
        self._pos.append(np.asarray(pos, dtype=np.float64))
        self._mass.append(float(mass))
        self._inertia.append(np.asarray(inertia, dtype=np.float64))
        return idx

    def body_index(self, name: str) -> int:
        return self._names.index(name)

    @property
    def body_positions(self) -> np.ndarray:
        return np.stack(self._pos)

    # -- joints ------------------------------------------------------------
    def add_joint(
        self,
        parent: str,
        child: str,
        world_anchor,
        *,
        free_axes: Sequence[str],
        limits: Sequence[Tuple[float, float]],
        gears: Sequence[float],
        axes: Optional[np.ndarray] = None,
        tone: Optional[float] = None,
    ):
        """``free_axes`` names rows of ``axes`` (default world x/y/z) that are
        free DOF, in action order; ``limits``/``gears`` align with them.
        ``tone`` (Nm/rad) overrides the default passive spring toward the
        reference pose on this joint's free axes — posture joints that must
        resist inverted-pendulum gravity torques passively need more than the
        inertia-scaled default."""
        if not (len(free_axes) == len(limits) == len(gears)):
            raise ValueError(
                f"free_axes/limits/gears must align: got {len(free_axes)}/"
                f"{len(limits)}/{len(gears)} for joint {parent}->{child}"
            )
        p = self.body_index(parent)
        c = self.body_index(child)
        anchor = np.asarray(world_anchor, dtype=np.float64)
        axes = _orthonormal_axes() if axes is None else np.asarray(axes, dtype=np.float64)
        name_to_row = {"x": 0, "y": 1, "z": 2}
        free = np.zeros(3)
        lo = np.zeros(3)
        hi = np.zeros(3)
        gear = np.zeros(3)
        order = []
        for ax_name, (l, u), g in zip(free_axes, limits, gears):
            row = name_to_row[ax_name]
            free[row] = 1.0
            lo[row], hi[row] = float(l), float(u)
            gear[row] = float(g)
            order.append(row)
        self._joints.append(
            dict(
                parent=p,
                child=c,
                anchor_p=anchor - self._pos[p],
                anchor_c=anchor - self._pos[c],
                axes=axes,
                free=free,
                lo=lo,
                hi=hi,
                gear=gear,
                order=order,
                tone=tone,
            )
        )

    # -- colliders ---------------------------------------------------------
    def add_sphere(self, body: str, world_center, radius: float):
        b = self.body_index(body)
        center = np.asarray(world_center, dtype=np.float64)
        self._spheres.append((b, center - self._pos[b], float(radius)))

    # -- finalize ----------------------------------------------------------
    def build(self) -> Tuple[System, jnp.ndarray]:
        """Returns ``(system, default_pose_positions)``; action indices are
        assigned in joint declaration order, then per-joint axis order."""
        def stack(key_or_rows, shape):
            rows = (
                [s[key_or_rows] for s in self._joints]
                if isinstance(key_or_rows, str)
                else key_or_rows
            )
            if not rows:
                return np.zeros((0,) + shape)
            return np.stack(rows)

        nj = len(self._joints)
        act_index = np.full((nj, 3), -1, dtype=np.int64)
        n_act = 0
        for j, spec in enumerate(self._joints):
            for row in spec["order"]:
                act_index[j, row] = n_act
                n_act += 1
        act_index[act_index < 0] = n_act  # points at the appended zero action

        # per-joint gains from target frequencies x reduced mass/inertia
        masses = np.asarray(self._mass)
        i_mean = np.stack(self._inertia).mean(axis=1)
        jp = np.asarray([s["parent"] for s in self._joints], dtype=np.int64)
        jc = np.asarray([s["child"] for s in self._joints], dtype=np.int64)
        # constraint-space effective mass: anchor forces also spin the bodies
        # through their lever arms (r^2/I), which for slender bodies dominates
        # 1/m — ignoring it puts the rotational response of light links past
        # the explicit-integration stability bound.
        r_p2 = np.sum(stack("anchor_p", (3,)) ** 2, axis=1)
        r_c2 = np.sum(stack("anchor_c", (3,)) ** 2, axis=1)
        inv_m_eff = 1.0 / masses[jp] + 1.0 / masses[jc] + r_p2 / i_mean[jp] + r_c2 / i_mean[jc]
        m_eff = 1.0 / inv_m_eff
        # per-axis reduced inertia: joint axes are world-aligned in the
        # reference pose, so axis a pairs with inertia component a of each body
        inertias = np.stack(self._inertia)
        i_red = inertias[jp] * inertias[jc] / (inertias[jp] + inertias[jc])  # (nj, 3)
        P = self._params
        pos_k = P["omega_pos"] ** 2 * m_eff
        pos_c = 2.0 * P["zeta"] * P["omega_pos"] * m_eff
        ang_k = P["omega_ang"] ** 2 * i_red
        ang_c = 2.0 * P["zeta"] * P["omega_ang"] * i_red

        f32 = jnp.float32
        sys = System(
            mass=jnp.asarray(self._mass, dtype=f32),
            inertia=jnp.asarray(np.stack(self._inertia), dtype=f32),
            joint_parent=jp,
            joint_child=jc,
            anchor_p=jnp.asarray(stack("anchor_p", (3,)), dtype=f32),
            anchor_c=jnp.asarray(stack("anchor_c", (3,)), dtype=f32),
            axes=jnp.asarray(stack("axes", (3, 3)), dtype=f32),
            free=jnp.asarray(stack("free", (3,)), dtype=f32),
            limit_lo=jnp.asarray(stack("lo", (3,)), dtype=f32),
            limit_hi=jnp.asarray(stack("hi", (3,)), dtype=f32),
            gear=jnp.asarray(stack("gear", (3,)), dtype=f32),
            act_index=act_index,
            num_act=n_act,
            act_mode=P["act_mode"],
            act_kp=jnp.asarray(P["act_kp_ratio"] * ang_k, dtype=f32),
            act_kd=jnp.asarray(P["act_kd_ratio"] * ang_c, dtype=f32),
            sph_body=np.asarray([s[0] for s in self._spheres], dtype=np.int64),
            sph_offset=jnp.asarray(stack([s[1] for s in self._spheres], (3,)), dtype=f32),
            sph_radius=jnp.asarray(np.asarray([s[2] for s in self._spheres]), dtype=f32),
            pos_k=jnp.asarray(pos_k, dtype=f32),
            pos_c=jnp.asarray(pos_c, dtype=f32),
            ang_k=jnp.asarray(ang_k, dtype=f32),
            ang_c=jnp.asarray(ang_c, dtype=f32),
            limit_k=jnp.asarray(P["limit_gain"] * ang_k, dtype=f32),
            tone_k=jnp.asarray(
                stack(
                    [
                        P["tone_ratio"] * k if s["tone"] is None else np.full(3, s["tone"])
                        for k, s in zip(ang_k, self._joints)
                    ],
                    (3,),
                ),
                dtype=f32,
            ),
            joint_damping=jnp.asarray(P["free_damping_ratio"] * ang_c, dtype=f32),
            gravity=jnp.asarray(P["gravity"], dtype=f32),
            contact_k=P["contact_k"],
            contact_c=P["contact_c"],
            friction_mu=P["friction_mu"],
            tangent_damping=P["tangent_damping"],
            max_vel=P["max_vel"],
            max_ang=P["max_ang"],
        )
        return sys, jnp.asarray(self.body_positions, dtype=f32)
