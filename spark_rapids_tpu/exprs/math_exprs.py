"""Math expressions (reference `mathExpressions.scala`).

All unary transcendentals produce float64 like Spark.  The reference
keeps a second, "improved" implementation of some float ops behind a
switch (GpuOverrides.scala:648-672); here there is one implementation,
XLA's libm lowering, and no switch.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.vector import ColumnVector
from spark_rapids_tpu.exprs.base import (
    BinaryExpression, Expression, UnaryExpression, promote)


@dataclasses.dataclass(eq=False)
class _UnaryMath(UnaryExpression):
    child: Expression

    def data_type(self, schema):
        return T.FLOAT64

    def do_columnar(self, c, ctx):
        x = c.data.astype(jnp.float64)
        return ColumnVector(T.FLOAT64, self.op(x), c.validity)


class Sqrt(_UnaryMath):
    def op(self, x): return jnp.sqrt(x)


class Cbrt(_UnaryMath):
    def op(self, x): return jnp.cbrt(x)


class Exp(_UnaryMath):
    def op(self, x): return jnp.exp(x)


class Expm1(_UnaryMath):
    def op(self, x): return jnp.expm1(x)


class Log(_UnaryMath):
    def op(self, x): return jnp.log(x)


class Log1p(_UnaryMath):
    def op(self, x): return jnp.log1p(x)


class Log2(_UnaryMath):
    def op(self, x): return jnp.log2(x)


class Log10(_UnaryMath):
    def op(self, x): return jnp.log10(x)


class Sin(_UnaryMath):
    def op(self, x): return jnp.sin(x)


class Cos(_UnaryMath):
    def op(self, x): return jnp.cos(x)


class Tan(_UnaryMath):
    def op(self, x): return jnp.tan(x)


class Asin(_UnaryMath):
    def op(self, x): return jnp.arcsin(x)


class Acos(_UnaryMath):
    def op(self, x): return jnp.arccos(x)


class Atan(_UnaryMath):
    def op(self, x): return jnp.arctan(x)


class Sinh(_UnaryMath):
    def op(self, x): return jnp.sinh(x)


class Cosh(_UnaryMath):
    def op(self, x): return jnp.cosh(x)


class Tanh(_UnaryMath):
    def op(self, x): return jnp.tanh(x)


class ToDegrees(_UnaryMath):
    def op(self, x): return jnp.degrees(x)


class ToRadians(_UnaryMath):
    def op(self, x): return jnp.radians(x)


class Rint(_UnaryMath):
    def op(self, x): return jnp.rint(x)


@dataclasses.dataclass(eq=False)
class Signum(_UnaryMath):
    child: Expression

    def op(self, x): return jnp.sign(x)


@dataclasses.dataclass(eq=False)
class Ceil(UnaryExpression):
    child: Expression

    def data_type(self, schema):
        return T.INT64

    def do_columnar(self, c, ctx):
        x = jnp.ceil(c.data.astype(jnp.float64))
        return ColumnVector(T.INT64, x.astype(jnp.int64), c.validity)


@dataclasses.dataclass(eq=False)
class Floor(UnaryExpression):
    child: Expression

    def data_type(self, schema):
        return T.INT64

    def do_columnar(self, c, ctx):
        x = jnp.floor(c.data.astype(jnp.float64))
        return ColumnVector(T.INT64, x.astype(jnp.int64), c.validity)


@dataclasses.dataclass(eq=False)
class Pow(BinaryExpression):
    left: Expression
    right: Expression

    def data_type(self, schema):
        return T.FLOAT64

    def do_columnar(self, l, r, ctx):
        a = l.data.astype(jnp.float64)
        b = r.data.astype(jnp.float64)
        return ColumnVector(T.FLOAT64, jnp.power(a, b),
                            l.validity & r.validity)


@dataclasses.dataclass(eq=False)
class Atan2(BinaryExpression):
    left: Expression
    right: Expression

    def data_type(self, schema):
        return T.FLOAT64

    def do_columnar(self, l, r, ctx):
        a = l.data.astype(jnp.float64)
        b = r.data.astype(jnp.float64)
        return ColumnVector(T.FLOAT64, jnp.arctan2(a, b),
                            l.validity & r.validity)


@dataclasses.dataclass(eq=False)
class Round(Expression):
    """HALF_UP rounding like Spark's round()."""
    child: Expression
    scale: int = 0

    def data_type(self, schema):
        return self.child.data_type(schema)

    def children(self):
        return (self.child,)

    def with_children(self, kids):
        return Round(kids[0], self.scale)

    def eval(self, ctx):
        c = self.child.eval(ctx)
        if c.dtype.is_integral and self.scale >= 0:
            return c
        if c.dtype.is_integral:
            # negative scale on integers: exact integer arithmetic — a
            # float64 round trip corrupts values beyond 2^53
            p = jnp.asarray(10 ** (-self.scale), c.data.dtype)
            half = p // 2
            v = c.data
            adj = jnp.where(v >= 0, v + half, v - half)
            from jax import lax
            out = lax.div(adj, p) * p
            return ColumnVector(c.dtype, out, c.validity)
        x = c.data.astype(jnp.float64)
        mul = 10.0 ** self.scale
        scaled = x * mul
        # HALF_UP: round half away from zero
        r = jnp.where(scaled >= 0, jnp.floor(scaled + 0.5),
                      jnp.ceil(scaled - 0.5))
        out = r / mul
        return ColumnVector(c.dtype, out.astype(c.dtype.storage_dtype),
                            c.validity)


class Cot(_UnaryMath):
    """cot(x) = 1/tan(x) (reference mathExpressions.scala GpuCot)."""
    def op(self, x): return 1.0 / jnp.tan(x)


class Acosh(_UnaryMath):
    """acosh (reference improved-float family GpuAcosh)."""
    def op(self, x): return jnp.arccosh(x)


class Asinh(_UnaryMath):
    def op(self, x): return jnp.arcsinh(x)


class Atanh(_UnaryMath):
    def op(self, x): return jnp.arctanh(x)


@dataclasses.dataclass(eq=False)
class Logarithm(BinaryExpression):
    """log(base, x) (reference GpuLogarithm): ln(x)/ln(base)."""
    left: Expression   # base
    right: Expression  # value

    def data_type(self, schema):
        return T.FLOAT64

    def do_columnar(self, l, r, ctx):
        base = l.data.astype(jnp.float64)
        val = r.data.astype(jnp.float64)
        out = jnp.log(val) / jnp.log(base)
        return ColumnVector(T.FLOAT64, out, l.validity & r.validity)
