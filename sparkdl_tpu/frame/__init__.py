"""Arrow-backed columnar DataFrame — the Spark-DataFrame stand-in.

The reference's entire API surface is ``Transformer.transform(df) -> df`` over
Spark DataFrames.  The TPU framework is Spark-independent: this module gives a
small pyarrow-Table-backed DataFrame with the operations the pipeline stages
need (select / withColumn / repartition / batch iteration), so the framework
runs standalone; when pyspark is present the same stages can be bridged via
pandas-UDFs (see ``sparkdl_tpu.udf``).
"""

from sparkdl_tpu.frame.dataframe import (DataFrame, Row, list_column,
                                         list_values_nbytes)

__all__ = ["DataFrame", "Row", "list_column", "list_values_nbytes"]
