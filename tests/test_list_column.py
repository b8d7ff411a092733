"""``frame.list_column`` (ISSUE 34): the output column of every stage is
built from the model's output matrix as Arrow buffers.  The reference
throughout is the plain construction it replaced — ``pa.array`` of
Python lists of Python floats — written out here."""

import tracemalloc

import numpy as np
import pyarrow as pa
import pytest

from sparkdl_tpu import obs
from sparkdl_tpu.frame import DataFrame, list_column, list_values_nbytes
from sparkdl_tpu.frame import dataframe as dataframe_module

FLOAT_LIST = pa.list_(pa.float32())


def reference(mat, valid_idx=None, num_rows=None):
    """The loop the packer replaced: one Python float a value."""
    mat = np.asarray(mat)
    flat = mat.reshape(len(mat), int(np.prod(mat.shape[1:])))
    n = len(flat) if num_rows is None else num_rows
    idx = range(len(flat)) if valid_idx is None else valid_idx
    values = [None] * n
    for row, i in zip(flat, idx):
        values[i] = [float(v) for v in row]
    return pa.array(values, type=FLOAT_LIST)


def _matrix(rows, width, dtype=np.float32, seed=34):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((rows, width)) * 1e3).astype(dtype)


def _specials():
    mat = _matrix(4, 6)
    mat[0, 0], mat[1, 2], mat[2, 5], mat[3, 3] = (
        np.nan, np.inf, -np.inf, -0.0)
    return mat


#: name, matrix, valid_idx, num_rows
CASES = [
    ("float32", _matrix(7, 5), None, None),
    ("float64 rounds as the loop does", _matrix(7, 5, np.float64), None,
     None),
    ("float16", _matrix(7, 5, np.float16), None, None),
    ("int32", np.arange(12, dtype=np.int32).reshape(3, 4), None, None),
    ("a slice that is not contiguous", _matrix(9, 12)[1::2, 2::3], None,
     None),
    ("a transposed view", _matrix(5, 7).T, None, None),
    ("rank 1: rows of one value", _matrix(6, 1).reshape(6), None, None),
    ("rank 3", _matrix(6, 8).reshape(6, 2, 4), None, None),
    ("rank 3 not contiguous", _matrix(6, 8).reshape(6, 2, 4)[:, :, ::2],
     None, None),
    ("NaN, both infinities, minus zero", _specials(), None, None),
    ("gaps", _matrix(3, 4), [1, 4, 5], 8),
    ("nulls at both ends", _matrix(2, 4), [1, 2], 4),
    ("valid at both ends", _matrix(2, 4), [0, 3], 4),
    ("valid_idx an array", _matrix(3, 4), np.array([0, 2, 5]), 6),
    ("every row valid, by valid_idx", _matrix(3, 4), [0, 1, 2], 3),
    ("no row valid", np.zeros((0, 4), np.float32), [], 5),
    ("no row valid, width unknown", np.zeros((0, 0), np.float32), [], 2),
    ("num_rows 0", np.zeros((0, 4), np.float32), [], 0),
    ("num_rows 0, no valid_idx", np.zeros((0, 4), np.float32), None, None),
    ("width 0", np.zeros((3, 0), np.float32), None, None),
    ("width 0 with nulls", np.zeros((2, 0), np.float32), [0, 2], 4),
]


@pytest.mark.parametrize("mat,valid_idx,num_rows",
                         [c[1:] for c in CASES], ids=[c[0] for c in CASES])
def test_equals_the_plain_construction(mat, valid_idx, num_rows):
    col = list_column(mat, valid_idx, num_rows)
    want = reference(mat, valid_idx, num_rows)
    assert isinstance(col, pa.ListArray)
    assert col.type == FLOAT_LIST and str(col.type) == "list<item: float>"
    assert col.null_count == want.null_count
    assert col.is_valid().equals(want.is_valid())
    assert col.offsets.equals(want.offsets)
    # Arrow's equals() holds a NaN unequal to itself: the bits below
    # speak for that case
    assert col.equals(want) or np.isnan(np.asarray(mat, np.float64)).any()
    col.validate(full=True)
    # bit for bit, NaN and the sign of zero included
    got_bits = np.asarray(col.flatten()).view(np.uint32)
    want_bits = np.asarray(want.flatten()).view(np.uint32)
    assert np.array_equal(got_bits, want_bits)
    # a null row takes no values
    assert len(col.values) == sum(r is not None for r in want.to_pylist()) \
        * int(np.prod(np.shape(mat)[1:]))


@pytest.mark.parametrize("source", ["contiguous float32", "float64",
                                    "strided"])
def test_the_column_owns_its_bytes(source):
    """A write into the source after packing does not show."""
    mat = {"contiguous float32": lambda: _matrix(6, 4),
           "float64": lambda: _matrix(6, 4, np.float64),
           "strided": lambda: _matrix(12, 8)[::2, ::2]}[source]()
    want = reference(mat, [0, 2, 3, 4, 6, 7], 8)
    col = list_column(mat, [0, 2, 3, 4, 6, 7], 8)
    mat[...] = -1.0
    assert col.equals(want)


@pytest.mark.parametrize("dtype", [np.float32, np.float64, np.float16])
def test_column_to_numpy_gives_the_matrix_back(dtype):
    mat = _matrix(9, 6, dtype)
    df = DataFrame({"id": list(range(9))}).withColumn("f", list_column(mat))
    back = df.column_to_numpy("f")
    assert back.dtype == np.float32
    assert np.array_equal(back, mat.astype(np.float32))


@pytest.mark.parametrize("mat,valid_idx,num_rows,error", [
    (np.float32(4.0), None, None, ValueError),               # rank 0
    (np.zeros((2, 3), np.complex64), None, None, TypeError),
    (np.zeros((2, 3), np.float32), None, 5, ValueError),     # rows short
    (np.zeros((2, 3), np.float32), [0], 5, ValueError),      # idx short
    (np.zeros((2, 3), np.float32), [3, 1], 5, ValueError),   # descending
    (np.zeros((2, 3), np.float32), [1, 1], 5, ValueError),   # repeated
    (np.zeros((2, 3), np.float32), [1, 5], 5, ValueError),   # past the end
    (np.zeros((2, 3), np.float32), [-1, 2], 5, ValueError),
], ids=["rank 0", "complex", "rows without valid_idx", "valid_idx short",
        "descending", "repeated", "past the end", "negative"])
def test_what_it_refuses(mat, valid_idx, num_rows, error):
    with pytest.raises(error):
        list_column(mat, valid_idx, num_rows)


@pytest.mark.parametrize("limit,chunk_rows", [
    (12, [4, 3, 3]),     # 3 valid rows of 4 values a chunk
    (4, [1, 2, 1, 1, 1, 1, 1, 1, 1]),   # one a chunk; the null rides along
    (35, [9, 1]),
    (36, [10]),          # everything fits: one array, not chunks
], ids=["12", "4", "35", "36"])
def test_past_the_offsets_limit_it_chunks(monkeypatch, limit, chunk_rows):
    """The docstring's promise, with the limit set small: chunks, each
    under the limit, slices of one copy; together the plain column."""
    monkeypatch.setattr(dataframe_module, "_LIST_VALUES_LIMIT", limit)
    # 9 valid of 10 rows: position 2 is null
    mat, valid_idx = _matrix(9, 4), [0, 1, 3, 4, 5, 6, 7, 8, 9]
    want = reference(mat, valid_idx, 10)
    col = list_column(mat, valid_idx, 10)
    chunks = col.chunks if isinstance(col, pa.ChunkedArray) else [col]
    assert isinstance(col, pa.ChunkedArray) == (len(chunk_rows) > 1)
    assert [len(c) for c in chunks] == chunk_rows
    assert all(len(c.values) <= limit for c in chunks)
    for c in chunks:
        c.validate(full=True)
    assert col.type == FLOAT_LIST
    assert pa.chunked_array(chunks).combine_chunks().equals(want)
    assert list_values_nbytes(col) == mat.size * 4
    # DataFrame.withColumn appends the chunks as they are
    df = DataFrame({"id": list(range(10))}).withColumn("f", col)
    assert df.table.column("f").num_chunks == len(chunk_rows)
    assert df.table.column("f").to_pylist() == want.to_pylist()
    assert np.array_equal(df.dropna("f").column_to_numpy("f"), mat)


def test_one_row_wider_than_the_limit_raises(monkeypatch):
    monkeypatch.setattr(dataframe_module, "_LIST_VALUES_LIMIT", 3)
    with pytest.raises(pa.ArrowCapacityError):
        list_column(_matrix(2, 4))


def test_packing_allocates_one_copy_and_no_python_floats():
    """No clock: a list of Python floats is some 8 times the matrix
    (24 bytes a float and 8 a pointer); the packer's peak is the one
    float32 copy and the offsets."""
    mat = _matrix(512, 2048)
    valid_idx = list(range(1, 513))
    list_column(mat[:8], valid_idx[:8], 9)      # imports, first calls
    tracemalloc.start()
    try:
        col = list_column(mat, valid_idx, 514)
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert col.null_count == 2
    assert peak < 1.5 * mat.nbytes
    assert list_values_nbytes(col) == mat.nbytes


# -- the four public stages that write such a column -----------------------

class _TinyZooModule:
    """A zoo module's surface over a trivial function of the input."""

    def apply(self, variables, x, train=False, features=False):
        import jax.numpy as jnp

        m = jnp.mean(x, axis=(1, 2, 3))
        idx = jnp.arange(2048 if features else 1000, dtype=jnp.float32)
        return m[:, None] * 0.01 + idx[None, :] * 1e-4


@pytest.fixture(scope="module")
def image_frame(tmp_path_factory):
    """Five rows, the third a file that does not decode: a null row."""
    from PIL import Image

    from sparkdl_tpu.image import io as image_io

    rng = np.random.default_rng(34)
    d = tmp_path_factory.mktemp("jpegs")
    for i in (0, 1, 3, 4):
        arr = (rng.random((20, 24, 3)) * 255).astype("uint8")
        Image.fromarray(arr).save(d / f"img_{i}.jpg", quality=90)
    (d / "img_2.jpg").write_bytes(b"no jpeg")
    df = image_io.readImages(str(d), numPartitions=1)
    assert [r["image"] is None for r in df.collect()] == [
        False, False, True, False, False]
    return df


def _zoo_stage(cls, image_frame, monkeypatch):
    from sparkdl_tpu.transformers import named_image as ni

    monkeypatch.setitem(ni._MODEL_CACHE, "ResNet50",
                        (_TinyZooModule(), {}))
    monkeypatch.setattr(ni, "_ENGINE_CACHE", {})
    stage = cls(inputCol="image", outputCol="out", modelName="ResNet50",
                batchSize=4)
    return ni, stage, image_frame, "out", 1


def _featurizer(image_frame, monkeypatch):
    from sparkdl_tpu.transformers import DeepImageFeaturizer

    return _zoo_stage(DeepImageFeaturizer, image_frame, monkeypatch)


def _predictor(image_frame, monkeypatch):
    from sparkdl_tpu.transformers import DeepImagePredictor

    return _zoo_stage(DeepImagePredictor, image_frame, monkeypatch)


def _model_transformer(image_frame, monkeypatch):
    import jax.numpy as jnp

    from sparkdl_tpu.graph.function import ModelFunction
    from sparkdl_tpu.transformers import ModelTransformer, tensor

    mf = ModelFunction(fn=lambda v, t: jnp.tanh(t @ v["w"]),
                       variables={"w": _matrix(6, 3) / 1e3})
    df = DataFrame(pa.table({"x": list_column(_matrix(5, 6) / 1e3)}))
    stage = ModelTransformer(inputCol="x", outputCol="out",
                             modelFunction=mf, batchSize=4)
    return tensor, stage, df, "out", 0


def _logistic_model(image_frame, monkeypatch):
    from sparkdl_tpu.estimators import classification

    # float64 weights: the probabilities reach the packer as float64
    model = classification.LogisticRegressionModel(
        weights={"w": _matrix(6, 3, np.float64) / 1e3,
                 "b": np.zeros(3)}, numClasses=3)
    df = DataFrame(pa.table({"features": list_column(_matrix(5, 6) / 1e3)}))
    return classification, model, df, "probability", None


@pytest.mark.parametrize("make", [_featurizer, _predictor,
                                  _model_transformer, _logistic_model],
                         ids=lambda f: f.__name__.strip("_"))
def test_a_stage_writes_its_column_from_the_buffer(make, image_frame,
                                                   monkeypatch):
    """The column a stage appends ``equals()`` the plain construction
    over the very matrix the stage handed the packer, null row and all;
    where the stage opens ``transform.pack_out`` the span says that no
    value was a Python object."""
    module, stage, df, name, null_rows = make(image_frame, monkeypatch)
    packed = []

    def recording(mat, valid_idx=None, num_rows=None):
        packed.append((np.array(mat), valid_idx, num_rows))
        return list_column(mat, valid_idx, num_rows)

    monkeypatch.setattr(module, "list_column", recording)
    tracer = obs.configure(enabled=True)
    try:
        out = stage.transform(df)
        spans = [s for s in tracer.snapshot()
                 if s["name"] == "transform.pack_out"]
    finally:
        obs.configure_from_env()
    (mat, valid_idx, num_rows), = packed
    col = out.table.column(name)
    assert col.type == FLOAT_LIST and len(col) == len(df)
    assert col.combine_chunks().equals(reference(mat, valid_idx, num_rows))
    assert col.null_count == (null_rows or 0)
    if null_rows is None:       # the fitted head opens no span
        assert spans == []
        return
    (span,) = spans
    assert span["attrs"] == {
        "rows": len(mat), "values": mat.size, "bytes": mat.size * 4,
        "null_rows": null_rows, "py_values": 0}


# -- the int32 column (ISSUE 37): ids stay integers --------------------------

INT_LIST = pa.list_(pa.int32())
INT_CASES = [
    ("int32", np.arange(12, dtype=np.int32).reshape(3, 4) - 5, None, None),
    ("int64 ids fit int32", np.arange(6, dtype=np.int64).reshape(2, 3) * 70000,
     None, None),
    ("nulls at both ends", np.arange(8, dtype=np.int32).reshape(2, 4), [1, 2],
     4),
    ("gaps", np.arange(12, dtype=np.int32).reshape(3, 4), [1, 4, 5], 8),
    ("an empty frame", np.zeros((0, 4), np.int32), None, None),
    ("no row valid", np.zeros((0, 4), np.int32), [], 3),
    ("rank 1: rows of one id", np.arange(5, dtype=np.int32), None, None),
]


@pytest.mark.parametrize("mat,valid_idx,num_rows",
                         [c[1:] for c in INT_CASES],
                         ids=[c[0] for c in INT_CASES])
def test_an_int32_column_equals_the_plain_construction(mat, valid_idx,
                                                       num_rows):
    col = list_column(mat, valid_idx, num_rows, dtype=np.int32)
    mat = np.asarray(mat)
    flat = mat.reshape(len(mat), int(np.prod(mat.shape[1:])))
    n = len(flat) if num_rows is None else num_rows
    values = [None] * n
    for row, i in zip(flat, range(len(flat)) if valid_idx is None
                      else valid_idx):
        values[i] = [int(v) for v in row]
    assert col.type == INT_LIST
    assert col.equals(pa.array(values, type=INT_LIST))
    col.validate(full=True)


def test_an_int32_column_round_trips_through_the_frame():
    ids = np.arange(15, dtype=np.int32).reshape(5, 3) * 1000003 % 151936
    df = DataFrame({"id": list(range(5))}).withColumn(
        "ids", list_column(ids, dtype=np.int32))
    back = df.column_to_numpy("ids")
    assert back.dtype == np.int32
    np.testing.assert_array_equal(back, ids)
    assert [r["ids"] for r in df.collect()] == ids.tolist()


def test_an_int32_column_in_chunks(monkeypatch):
    """Past the values one list array's offsets address the column comes
    back in chunks, as the float column does."""
    monkeypatch.setattr(dataframe_module, "_LIST_VALUES_LIMIT", 10)
    ids = np.arange(28, dtype=np.int32).reshape(7, 4)
    col = list_column(ids, dtype=np.int32)
    assert isinstance(col, pa.ChunkedArray) and col.num_chunks == 4
    assert col.type == INT_LIST
    df = DataFrame(pa.table({"ids": col}))
    np.testing.assert_array_equal(df.column_to_numpy("ids"), ids)


def test_only_float32_and_int32_columns_are_made():
    with pytest.raises(TypeError, match="float32"):
        list_column(np.zeros((2, 2)), dtype=np.float64)
