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

import functools
from types import SimpleNamespace
from typing import List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

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
# Dynamics — one implementation, on per-body per-component rows
# ---------------------------------------------------------------------------
#
# The state of a population is batch-trailing, ``(nb, comp, B)``: the
# population fills the 128 lanes of a TPU vector register. The arithmetic of
# a substep is written ONCE (``_substep_rows``) as plain elementwise
# operations on *rows*: one array per body and component, picked with static
# Python indices. Joint endpoints pick rows, the scatter of a joint's wrench
# onto its two bodies is the two additions it stands for, and every model
# constant is a Python float, so a structural zero (an anchor on an axis, a
# locked DOF, identity joint axes) costs nothing. Such a function does not
# care what shape a row has, and two callers hand it rows:
#
# - the plain form (``_plain_step``): rows are ``(B,)`` slices under ``jit``.
#   The CPU, small populations and the single-instance API (its ``B = 1``
#   case) run it, and so does the benchmark's plain reference;
# - the fused form (``_fused_step``): one Pallas kernel over blocks of 1,024
#   lanes, in which a row is one full ``(8, 128)`` register. A block's state
#   and actions are read from HBM once, all ``substeps`` substeps run on it
#   with every intermediate in registers or VMEM, and the new state is
#   written once. XLA cut the same control step into ~3,500 small fusions
#   with a pass through memory between them (PERF.md, PR 29).
#
# ``physics_step_batched`` chooses by what it can observe: the fused form
# where the program is lowered for a TPU and there is at least one block of
# lanes, the plain form everywhere else. No flag selects either.

_LANES = 128  # a vector register holds (8 sublanes, 128 lanes) of float32
_BLOCK = 8 * _LANES  # lanes a kernel block steps: every row one full register
FUSED_KERNEL_NAME = "rigidbody_fused_step"


class _Row:
    """A row under arithmetic. Operators and the few functions below bind
    ``lax`` primitives directly: ``jnp``'s operators trace a jitted ufunc per
    call (0.2-0.7 ms each), and at ~4,500 operations a substep, traced once
    per form and program, that was tens of seconds of a run's set-up on the
    chip's host (PERF.md, PR 29). The other operand is a row or a Python
    number, and a model constant that is exactly 0 or +-1 costs no operation:
    ``row * 0.0`` is the Python ``0.0``, which the next operator drops in
    turn. Comparisons give rows of booleans for ``_where``."""

    __slots__ = ("x",)

    def __init__(self, x):
        self.x = x

    def __add__(self, other):
        return self if _is_zero(other) else _Row(lax.add(self.x, _raw(other)))

    def __sub__(self, other):
        return self if _is_zero(other) else _Row(lax.sub(self.x, _raw(other)))

    def __rsub__(self, other):
        return -self if _is_zero(other) else _Row(lax.sub(other, self.x))

    def __mul__(self, other):
        if _is_zero(other):
            return 0.0
        if isinstance(other, float) and abs(other) == 1.0:
            return self if other > 0 else -self
        return _Row(lax.mul(self.x, _raw(other)))

    def __truediv__(self, other):
        return _Row(lax.div(self.x, _raw(other)))

    def __rtruediv__(self, other):
        return _Row(lax.div(other, self.x))

    def __neg__(self):
        return _Row(lax.neg(self.x))

    def __lt__(self, other):
        return _Row(lax.lt(self.x, _raw(other)))

    def __gt__(self, other):
        return _Row(lax.gt(self.x, _raw(other)))

    def __ge__(self, other):
        return _Row(lax.ge(self.x, _raw(other)))

    __radd__, __rmul__ = __add__, __mul__


def _is_zero(value) -> bool:
    return isinstance(value, (int, float)) and value == 0


def _raw(value):
    return value.x if isinstance(value, _Row) else value


def _where(mask: _Row, a, b) -> _Row:
    """``a`` where ``mask`` else ``b``; either may be a Python float."""
    a, b = _raw(a), _raw(b)
    if isinstance(a, float):
        a = lax.full_like(b, a)
    elif isinstance(b, float):
        b = lax.full_like(a, b)
    return _Row(lax.select(mask.x, a, b))


def _maximum(a: _Row, b) -> _Row:
    return _Row(lax.max(a.x, _raw(b)))


def _minimum(a: _Row, b) -> _Row:
    return _Row(lax.min(a.x, _raw(b)))


def _clip(a: _Row, lo: float, hi: float) -> _Row:
    return _Row(lax.clamp(lo, a.x, hi))


def _sqrt(a: _Row) -> _Row:
    return _Row(lax.sqrt(a.x))


def _abs(a: _Row) -> _Row:
    return _Row(lax.abs(a.x))


def _atan2(s: _Row, w: _Row) -> _Row:
    return _Row(lax.atan2(s.x, w.x))


def _dot(a, b):
    return sum(x * y for x, y in zip(a, b))


def _cross(a, b):
    return [
        a[1] * b[2] - a[2] * b[1],
        a[2] * b[0] - a[0] * b[2],
        a[0] * b[1] - a[1] * b[0],
    ]


def _rotate(R, v):
    return [_dot(R[i], v) for i in range(3)]


def _rotate_inv(R, v):
    return [_dot((R[0][i], R[1][i], R[2][i]), v) for i in range(3)]


def _rotation_rows(q):
    """The nine rows of a body's rotation matrix from its quaternion rows.
    Built once per substep: ~8 vectors are rotated per body (joint anchors,
    relative angular velocities, torques, contact offsets, the body-frame
    angular update) at 15 operations each instead of 30."""
    w, x, y, z = q
    xx, yy, zz = x * x, y * y, z * z
    xy, xz, yz = x * y, x * z, y * z
    wx, wy, wz = w * x, w * y, w * z
    return [
        [1.0 - 2.0 * (yy + zz), 2.0 * (xy - wz), 2.0 * (xz + wy)],
        [2.0 * (xy + wz), 1.0 - 2.0 * (xx + zz), 2.0 * (yz - wx)],
        [2.0 * (xz - wy), 2.0 * (yz + wx), 1.0 - 2.0 * (xx + yy)],
    ]


def _atan2_first_quadrant(s, w):
    """float32 ``atan2(s, w)`` for ``s >= 0`` and ``w >= 0``, from operations
    a Pallas TPU kernel can lower (``jnp.arctan2`` is not one): the range
    reduction and the odd polynomial of Cephes' ``atanf``, which libm's and
    XLA's own expansions use too. ``atan(lo / hi)`` on ``[0, 1]``, moved by
    ``pi / 4`` above ``tan(pi / 8)`` with one division for both cases, and
    mirrored about ``pi / 4`` where ``s > w``. Within a few ulp of
    ``jnp.arctan2`` on the whole quadrant, edges included
    (``tests/test_rigidbody_fused.py``)."""
    hi = _maximum(s, w)
    lo = _minimum(s, w)
    shifted = lo > hi * 0.41421356237309503
    num = _where(shifted, lo - hi, lo)
    den = _where(shifted, lo + hi, hi)
    t = num / _maximum(den, 1e-37)  # atan2(0, 0) = 0
    z = t * t
    poly = (
        ((z * 8.05374449538e-2 - 1.38776856032e-1) * z + 1.99777106478e-1) * z
        - 3.33329491539e-1
    ) * z * t + t
    angle = _where(shifted, poly + 0.7853981633974483, poly)
    return _where(s > w, 1.5707963267948966 - angle, angle)


class _Model(SimpleNamespace):
    """A ``System`` as the row form reads it: every array a nested list of
    Python numbers, so model constants are literals of the traced program
    and of the kernel (a float32 is held exactly by a Python float). Hashes
    by content: two instances of one env (a problem and its frozen twin)
    share the programs cached below."""

    def __init__(self, sys: System):
        values = {}
        for name, value in jax.device_get(sys._asdict()).items():  # one transfer
            if isinstance(value, np.ndarray):
                if value.dtype.kind == "f":
                    value = value.astype(np.float64)
                value = value.tolist()
            values[name] = value
        super().__init__(**values)
        self._content = repr(sorted(values.items()))

    def __hash__(self):
        return hash(self._content)

    def __eq__(self, other):
        return self._content == other._content


@functools.lru_cache(maxsize=64)
def _substep_program(m: _Model, h: float, in_kernel: bool):
    """One substep on rows, jitted: a caller's loop body is then ONE traced
    call, and the ~4,500 operations behind it are traced once per model and
    row shape in the process. In the kernel a row is always ``(8, 128)``, so
    every program after the first (another lane count, the frozen twin of a
    problem) reuses the trace; tracing them per program inside a Pallas
    kernel cost 3 s each on the chip's host (PERF.md, PR 29)."""
    atan2 = _atan2_first_quadrant if in_kernel else _atan2
    return jax.jit(lambda rows, actions: _substep_rows(m, rows, actions, h, atan2))


def _joint_wrenches(m, j, pos, quat, vel, ang, R, actions, atan2, force, torque):
    """Joint ``j``'s constraint, limit and actuation wrench, added onto the
    ``force`` / ``torque`` rows of its two bodies."""
    p, c = m.joint_parent[j], m.joint_child[j]
    Rp = R[p]

    # positional constraint: pull the two anchor points together
    ra = _rotate(Rp, m.anchor_p[j])  # world lever arms
    rb = _rotate(R[c], m.anchor_c[j])
    va = _cross(ang[p], ra)
    vb = _cross(ang[c], rb)
    fj = []
    for k in range(3):
        err = (pos[c][k] + rb[k]) - (pos[p][k] + ra[k])
        verr = (vel[c][k] + vb[k]) - (vel[p][k] + va[k])
        fj.append(-m.pos_k[j] * err - m.pos_c[j] * verr)
    tb = _cross(rb, fj)
    ta = _cross(ra, fj)

    # angular: relative rotation conj(parent) * child, as a rotation vector
    # along the shortest arc, in the parent's frame
    aw, ax, ay, az = quat[p]
    bw, bx, by, bz = quat[c]
    w = aw * bw + ax * bx + ay * by + az * bz
    x = aw * bx - ax * bw - ay * bz + az * by
    y = aw * by + ax * bz - ay * bw - az * bx
    z = aw * bz - ax * by + ay * bx - az * bw
    s = _sqrt(x * x + y * y + z * z)
    angle = 2.0 * atan2(s, _abs(w))
    # angle/s -> 2/w as s -> 0; keep the division finite everywhere
    scale = _where(s < 1e-7, 2.0, angle / _maximum(s, 1e-12))
    scale = _where(w < 0.0, -scale, scale)  # q and -q are one rotation
    phi = [x * scale, y * scale, z * scale]
    w_rel = _rotate_inv(Rp, [ang[c][k] - ang[p][k] for k in range(3)])

    # components along the (orthonormal) joint axes; since the axes form a
    # complete basis, the whole angular response is expressed per component,
    # which lets every axis carry its own gain (a thigh's inertia about its
    # long axis is ~6x smaller than across it — shared gains would put the
    # twist axis past the explicit-integration stability bound)
    comp_torque = []
    for a in range(3):
        phi_a = _dot(m.axes[j][a], phi)
        w_a = _dot(m.axes[j][a], w_rel)
        free, gear = m.free[j][a], m.gear[j][a]
        lo, hi = m.limit_lo[j][a], m.limit_hi[j][a]
        torque_a = 0.0
        if free != 1.0:  # a locked axis: spring and damper toward the reference
            locked_t = -m.ang_k[j][a] * phi_a - m.ang_c[j][a] * w_a
            torque_a = (1.0 - free) * locked_t
        if free != 0.0:  # a free axis: limits, passive tone, damping, actuation
            over = _maximum(phi_a - hi, 0.0)
            under = _maximum(lo - phi_a, 0.0)
            free_t = (
                m.limit_k[j][a] * (under - over)
                - m.tone_k[j][a] * phi_a
                - m.joint_damping[j][a] * w_a
            )
            index = m.act_index[j][a]
            drive = actions[index] if index < m.num_act else 0.0
            if m.act_mode != "position":
                free_t = free_t + gear * drive
            elif gear > 0.0:
                # action in [-1, 1] maps to a target angle: 0 is the reference
                # pose, +/-1 the joint limits; a torque-clipped PD servo tracks it
                target = 0.0
                if not _is_zero(drive):
                    target = _where(drive >= 0.0, drive * hi, -drive * lo)
                pd = m.act_kp[j][a] * (target - phi_a) - m.act_kd[j][a] * w_a
                free_t = free_t + _clip(pd, -gear, gear)
            torque_a = torque_a + free * free_t
        comp_torque.append(torque_a)
    axes_t = [[m.axes[j][a][k] for a in range(3)] for k in range(3)]
    tau_w = _rotate(Rp, [_dot(axes_t[k], comp_torque) for k in range(3)])

    for k in range(3):  # force on child, reaction on parent
        force[c][k] = force[c][k] + fj[k]
        force[p][k] = force[p][k] - fj[k]
        torque[c][k] = torque[c][k] + tb[k] + tau_w[k]
        torque[p][k] = torque[p][k] - ta[k] - tau_w[k]


def _contact_wrench(m, i, pos, vel, ang, R, force, torque):
    """Sphere ``i`` against the ground: penalty normal force and clamped
    viscous friction, added onto its body's ``force`` / ``torque`` rows."""
    b, radius = m.sph_body[i], m.sph_radius[i]
    r_off = _rotate(R[b], m.sph_offset[i])
    pen = radius - (pos[b][2] + r_off[2])
    rel = [r_off[0], r_off[1], r_off[2] - radius]  # the sphere's lowest point
    spin = _cross(ang[b], rel)
    vc = [vel[b][k] + spin[k] for k in range(3)]

    fn = _maximum(m.contact_k * pen - m.contact_c * vc[2], 0.0)
    fn = _where(pen > 0.0, fn, 0.0)
    vt_norm = _sqrt(vc[0] * vc[0] + vc[1] * vc[1])
    # clamped viscous friction: viscous at small slip, Coulomb cap mu*N above
    ft_mag = _minimum(m.friction_mu * fn, m.tangent_damping * vt_norm)
    slip = ft_mag / _maximum(vt_norm, 1e-6)
    fc = [-(vc[0] * slip), -(vc[1] * slip), fn]
    tc = _cross(rel, fc)
    for k in range(3):
        force[b][k] = force[b][k] + fc[k]
        torque[b][k] = torque[b][k] + tc[k]


def _substep_rows(m, state, actions, h: float, atan2):
    """One semi-implicit Euler substep on rows. ``state`` is ``(pos, quat,
    vel, ang)``, each a list over bodies of lists over components; a row and
    each of the ``num_act`` ``actions`` is an array of one common shape, which
    this function never looks at: it wraps each in a ``_Row`` and hands the
    arrays back. ``atan2(s, w)`` takes and gives rows, and is asked for
    ``s, w >= 0`` only."""
    pos, quat, vel, ang = jax.tree_util.tree_map(_Row, state)
    actions = [_Row(a) for a in actions]
    nb = len(pos)
    # per-body rotation matrices, built ONCE and shared by every rotation in
    # the substep (joints, contacts, body-frame angular update)
    R = [_rotation_rows(q) for q in quat]
    force = [[0.0, 0.0, 0.0] for _ in range(nb)]
    torque = [[0.0, 0.0, 0.0] for _ in range(nb)]
    for j in range(len(m.joint_parent)):
        _joint_wrenches(m, j, pos, quat, vel, ang, R, actions, atan2, force, torque)
    for i in range(len(m.sph_body)):
        _contact_wrench(m, i, pos, vel, ang, R, force, torque)

    new_pos, new_quat, new_vel, new_ang = [], [], [], []
    for b in range(nb):
        # stability clamps: cap velocities so stiff-spring transients cannot blow up
        v = [
            _clip(
                vel[b][k] + (h / m.mass[b]) * force[b][k] + h * m.gravity[k],
                -m.max_vel,
                m.max_vel,
            )
            for k in range(3)
        ]
        # angular update in the body frame, where the inertia tensor is diagonal
        w_body = _rotate_inv(R[b], ang[b])
        tau_body = _rotate_inv(R[b], torque[b])
        gyro = _cross(w_body, [m.inertia[b][k] * w_body[k] for k in range(3)])
        w_body = [
            w_body[k] + (h / m.inertia[b][k]) * (tau_body[k] - gyro[k]) for k in range(3)
        ]
        w = [_clip(x, -m.max_ang, m.max_ang) for x in _rotate(R[b], w_body)]

        # first-order quaternion update from the world-frame angular velocity
        qw, qx, qy, qz = quat[b]
        half = 0.5 * h
        q = [
            qw + half * (-w[0] * qx - w[1] * qy - w[2] * qz),
            qx + half * (w[0] * qw + w[1] * qz - w[2] * qy),
            qy + half * (w[1] * qw + w[2] * qx - w[0] * qz),
            qz + half * (w[2] * qw + w[0] * qy - w[1] * qx),
        ]
        inv_norm = 1.0 / _sqrt(q[0] * q[0] + q[1] * q[1] + q[2] * q[2] + q[3] * q[3])
        new_pos.append([pos[b][k] + h * v[k] for k in range(3)])
        new_quat.append([x * inv_norm for x in q])
        new_vel.append(v)
        new_ang.append(w)
    return jax.tree_util.tree_map(_raw, (new_pos, new_quat, new_vel, new_ang))


def _to_rows(st: BodyState):
    return tuple([[x[b, k] for k in range(x.shape[1])] for b in range(x.shape[0])] for x in st)


def _from_rows(rows) -> BodyState:
    return BodyState(*(jnp.stack([jnp.stack(body) for body in field]) for field in rows))


def physics_substep_batched(
    sys: System, st: BodyState, actions: jnp.ndarray, h
) -> BodyState:
    """One semi-implicit Euler substep for a population: ``st`` arrays are
    ``(nb, comp, B)``, ``actions`` ``(num_act, B)``. The plain form."""
    rows = _substep_rows(_Model(sys), _to_rows(st), list(actions), h, _atan2)
    return _from_rows(rows)


@functools.lru_cache(maxsize=32)
def _plain_program(m: _Model, h: float, substeps: int):
    """The plain form's control step for one model: ``substeps`` substeps as
    a loop over rows. Jitted once, so that an eager caller (the
    single-instance API in a host loop) does not trace anew at every call;
    and with a batching rule of its own, so that ``vmap`` over instances (the
    ``B = 1`` API under ``vmap``: the benchmark's plain reference) puts the
    instances into the lane axis and steps them as the SAME plain program at
    ``B = N``, where re-interpreting a substep's traced operations under
    ``vmap`` cost the reference's set-up tens of seconds (PERF.md, PR 29)."""
    substep = _substep_program(m, h, False)

    @jax.custom_batching.custom_vmap
    def step(st, actions):
        act = [actions[i] for i in range(actions.shape[0])]
        # the loop carries the rows, not the stacked state
        rows = jax.lax.fori_loop(0, substeps, lambda _, rows: substep(rows, act), _to_rows(st))
        return _from_rows(rows)

    @step.def_vmap
    def instances_into_lanes(axis_size, in_batched, st, actions):
        def fold(x, batched):  # (N, ..., B) -> (..., N * B)
            if not batched:
                x = jnp.broadcast_to(x, (axis_size,) + x.shape)
            return jnp.moveaxis(x, 0, -2).reshape(x.shape[1:-1] + (-1,))

        def unfold(x):  # (..., N * B) -> (N, ..., B)
            return jnp.moveaxis(x.reshape(x.shape[:-1] + (axis_size, -1)), -2, 0)

        st = BodyState(*(fold(x, b) for x, b in zip(st, in_batched[0])))
        out = program(st, fold(actions, in_batched[1]))
        return BodyState(*(unfold(x) for x in out)), BodyState(True, True, True, True)

    program = jax.jit(step)
    return program


def _plain_step(sys: System, st: BodyState, actions, h: float, substeps: int) -> BodyState:
    return _plain_program(_Model(sys), h, substeps)(st, actions)


def _fused_lanes(lanes: int) -> int:
    """Lanes the kernel's grid computes for ``lanes`` useful ones: whole blocks."""
    return -(-lanes // _BLOCK) * _BLOCK


def _fused_local(sys, st, actions, h, substeps, interpret=False):
    """The fused form on the lanes one device holds: relayout into rows of
    whole ``(8, 128)`` registers, one kernel over blocks of lanes, and back.
    The tail block is padded with zeros; its lanes are never written back."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    substep = _substep_program(_Model(sys), h, True)
    B = actions.shape[-1]
    padded = _fused_lanes(B)
    sizes = [x.shape[0] * x.shape[1] for x in st]
    n_state, n_act = sum(sizes), actions.shape[0]
    rows = jnp.concatenate([x.reshape(-1, B) for x in st] + [actions], axis=0)
    rows = jnp.pad(rows, ((0, 0), (0, padded - B)))
    rows = rows.reshape(n_state + n_act, padded // _LANES, _LANES)

    # rows in the order of the concatenate above: field, body, component
    rows_of = jax.tree_util.tree_structure(
        tuple([[0] * x.shape[1] for _ in range(x.shape[0])] for x in st)
    )

    def kernel(in_ref, out_ref):
        act = [in_ref[n_state + i] for i in range(n_act)]
        rows = rows_of.unflatten([in_ref[r] for r in range(n_state)])
        rows = jax.lax.fori_loop(0, substeps, lambda _, rows: substep(rows, act), rows)
        for r, row in enumerate(jax.tree_util.tree_leaves(rows)):
            out_ref[r] = row

    out = pl.pallas_call(
        kernel,
        grid=(padded // _BLOCK,),
        in_specs=[pl.BlockSpec((n_state + n_act, 8, _LANES), lambda g: (0, g, 0))],
        out_specs=pl.BlockSpec((n_state, 8, _LANES), lambda g: (0, g, 0)),
        out_shape=jax.ShapeDtypeStruct((n_state, padded // _LANES, _LANES), rows.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        # the useful and the computed lanes, where the compiled program's text
        # shows them (benchmark/layer_metrics/env.fused_lanes_share.py)
        name=f"{FUSED_KERNEL_NAME}_{B}_of_{padded}",
        interpret=interpret,
    )(rows)
    fields = jnp.split(out.reshape(n_state, padded)[:, :B], np.cumsum(sizes)[:-1].tolist())
    return BodyState(*(rows.reshape(x.shape) for rows, x in zip(fields, st)))


def _fused_step(sys, st, actions, h, substeps, interpret=False):
    """The fused form on whatever devices the population is spread over. The
    SPMD partitioner cannot split a kernel and would gather every lane onto
    every device around one. Lanes are independent, so where the program is
    traced under a mesh (``parallel/evaluate.py`` traces its GSPMD programs
    under ``jax.sharding.use_abstract_mesh``; the population lies over all of
    its axes), a ``shard_map`` over the lane axis has every device step the
    lanes it holds: no collective is added."""
    from jax.sharding import AxisType, PartitionSpec

    def local(st, actions):
        return _fused_local(sys, st, actions, h, substeps, interpret)

    mesh = jax.sharding.get_abstract_mesh()
    axes = tuple(
        name
        for name, kind in zip(mesh.axis_names, mesh.axis_types)
        if kind == AxisType.Auto and mesh.shape[name] > 1
    )
    shards = int(np.prod([mesh.shape[name] for name in axes]))
    if shards == 1 or actions.shape[-1] % shards:
        return local(st, actions)
    lanes = PartitionSpec(None, None, axes)
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(BodyState(lanes, lanes, lanes, lanes), PartitionSpec(None, axes)),
        out_specs=BodyState(lanes, lanes, lanes, lanes),
        check_vma=False,
    )(st, actions)


def _by_platform(fused, plain, *args):
    """``fused`` where the program is lowered for a TPU, ``plain`` elsewhere.
    In a process whose default backend is the TPU that is known while
    tracing, and the plain form is not traced at all: a substep's ~4,500
    operations take seconds to trace on the chip's host, per form and row
    shape (PERF.md, PR 29). Elsewhere the lowering decides, so a compile for
    a described TPU from a CPU host holds the kernel and a CPU run the plain
    form."""
    if jax.default_backend() == "tpu":
        return fused(*args)
    return jax.lax.platform_dependent(*args, tpu=fused, default=plain)


def physics_step_batched(
    sys: System, st: BodyState, actions: jnp.ndarray, dt: float, substeps: int
) -> BodyState:
    """One control step = ``substeps`` substeps with the action held: ``st``
    arrays are ``(nb, comp, B)``, ``actions`` ``(num_act, B)``. One kernel
    where the program is lowered for a TPU and ``B`` fills at least one block
    of lanes (``_fused_step``), the plain form everywhere else."""
    h, substeps = dt / substeps, int(substeps)
    fits = (
        st.pos.ndim == 3
        and actions.shape[-1] >= _BLOCK
        and all(x.dtype == jnp.float32 for x in (*st, actions))
    )
    if not fits:
        return _plain_step(sys, st, actions, h, substeps)
    return _by_platform(
        lambda st, actions: _fused_step(sys, st, actions, h, substeps),
        lambda st, actions: _plain_step(sys, st, actions, h, substeps),
        st,
        actions,
    )


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


# batch-trailing quaternion helpers (component axis -2) of the measurements


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
