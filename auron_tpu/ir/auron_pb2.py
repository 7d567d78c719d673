# -*- coding: utf-8 -*-
# Generated by the protocol buffer compiler.  DO NOT EDIT!
# source: auron.proto
"""Generated protocol buffer code."""
from google.protobuf.internal import builder as _builder
from google.protobuf import descriptor as _descriptor
from google.protobuf import descriptor_pool as _descriptor_pool
from google.protobuf import symbol_database as _symbol_database
# @@protoc_insertion_point(imports)

_sym_db = _symbol_database.Default()




DESCRIPTOR = _descriptor_pool.Default().AddSerializedFile(b'\n\x0b\x61uron.proto\x12\x0c\x61uron_tpu.ir\"\xfd\x01\n\x06\x46ieldP\x12\x0c\n\x04name\x18\x01 \x01(\t\x12&\n\x05\x64type\x18\x02 \x01(\x0e\x32\x17.auron_tpu.ir.DataTypeP\x12\x10\n\x08nullable\x18\x03 \x01(\x08\x12\x11\n\tprecision\x18\x04 \x01(\r\x12\r\n\x05scale\x18\x05 \x01(\r\x12\x14\n\x0cstring_width\x18\x06 \x01(\r\x12%\n\x04\x65lem\x18\x07 \x01(\x0e\x32\x17.auron_tpu.ir.DataTypeP\x12$\n\x03key\x18\x08 \x01(\x0e\x32\x17.auron_tpu.ir.DataTypeP\x12&\n\x08\x63hildren\x18\t \x03(\x0b\x32\x14.auron_tpu.ir.FieldP\"/\n\x07SchemaP\x12$\n\x06\x66ields\x18\x01 \x03(\x0b\x32\x14.auron_tpu.ir.FieldP\"\x91\x06\n\x08\x45xprNode\x12*\n\x06\x63olumn\x18\x01 \x01(\x0b\x32\x18.auron_tpu.ir.ColumnRefEH\x00\x12)\n\x07literal\x18\x02 \x01(\x0b\x32\x16.auron_tpu.ir.LiteralEH\x00\x12\'\n\x06\x62inary\x18\x03 \x01(\x0b\x32\x15.auron_tpu.ir.BinaryEH\x00\x12%\n\x05unary\x18\x04 \x01(\x0b\x32\x14.auron_tpu.ir.UnaryEH\x00\x12#\n\x04\x63\x61st\x18\x05 \x01(\x0b\x32\x13.auron_tpu.ir.CastEH\x00\x12,\n\tcase_when\x18\x06 \x01(\x0b\x32\x17.auron_tpu.ir.CaseWhenEH\x00\x12(\n\x07in_list\x18\x07 \x01(\x0b\x32\x15.auron_tpu.ir.InListEH\x00\x12#\n\x04like\x18\x08 \x01(\x0b\x32\x13.auron_tpu.ir.LikeEH\x00\x12\x30\n\x0bstring_pred\x18\t \x01(\x0b\x32\x19.auron_tpu.ir.StringPredEH\x00\x12\x38\n\x0fscalar_function\x18\n \x01(\x0b\x32\x1d.auron_tpu.ir.ScalarFunctionEH\x00\x12)\n\x07nullary\x18\x0b \x01(\x0b\x32\x16.auron_tpu.ir.NullaryEH\x00\x12*\n\x08host_udf\x18\x0c \x01(\x0b\x32\x16.auron_tpu.ir.HostUDFEH\x00\x12?\n\x13\x62loom_might_contain\x18\r \x01(\x0b\x32 .auron_tpu.ir.BloomMightContainEH\x00\x12;\n\x11get_indexed_field\x18\x0e \x01(\x0b\x32\x1e.auron_tpu.ir.GetIndexedFieldEH\x00\x12\x39\n\x10get_struct_field\x18\x0f \x01(\x0b\x32\x1d.auron_tpu.ir.GetStructFieldEH\x00\x12\x38\n\x0fscalar_subquery\x18\x10 \x01(\x0b\x32\x1d.auron_tpu.ir.ScalarSubqueryEH\x00\x42\x06\n\x04\x65xpr\"\x8e\x01\n\x0fScalarSubqueryE\x12$\n\x04plan\x18\x01 \x01(\x0b\x32\x16.auron_tpu.ir.PlanNode\x12&\n\x05\x64type\x18\x02 \x01(\x0e\x32\x17.auron_tpu.ir.DataTypeP\x12\x11\n\tprecision\x18\x03 \x01(\r\x12\r\n\x05scale\x18\x04 \x01(\r\x12\x0b\n\x03sid\x18\x05 \x01(\r\")\n\nColumnRefE\x12\r\n\x05index\x18\x01 \x01(\x05\x12\x0c\n\x04name\x18\x02 \x01(\t\"\x9b\x01\n\x08LiteralE\x12&\n\x05\x64type\x18\x01 \x01(\x0e\x32\x17.auron_tpu.ir.DataTypeP\x12\x11\n\tprecision\x18\x02 \x01(\r\x12\r\n\x05scale\x18\x03 \x01(\r\x12\x0f\n\x07is_null\x18\x04 \x01(\x08\x12\r\n\x03i64\x18\x05 \x01(\x03H\x00\x12\r\n\x03\x66\x36\x34\x18\x06 \x01(\x01H\x00\x12\r\n\x03str\x18\x07 \x01(\tH\x00\x42\x07\n\x05value\"b\n\x07\x42inaryE\x12\n\n\x02op\x18\x01 \x01(\t\x12$\n\x04left\x18\x02 \x01(\x0b\x32\x16.auron_tpu.ir.ExprNode\x12%\n\x05right\x18\x03 \x01(\x0b\x32\x16.auron_tpu.ir.ExprNode\";\n\x06UnaryE\x12\n\n\x02op\x18\x01 \x01(\t\x12%\n\x05\x63hild\x18\x02 \x01(\x0b\x32\x16.auron_tpu.ir.ExprNode\"\x98\x01\n\x05\x43\x61stE\x12%\n\x05\x63hild\x18\x01 \x01(\x0b\x32\x16.auron_tpu.ir.ExprNode\x12&\n\x05\x64type\x18\x02 \x01(\x0e\x32\x17.auron_tpu.ir.DataTypeP\x12\x11\n\tprecision\x18\x03 \x01(\r\x12\r\n\x05scale\x18\x04 \x01(\r\x12\x10\n\x08try_cast\x18\x05 \x01(\x08\x12\x0c\n\x04\x61nsi\x18\x06 \x01(\x08\"\xbe\x01\n\tCaseWhenE\x12\x30\n\x08\x62ranches\x18\x01 \x03(\x0b\x32\x1e.auron_tpu.ir.CaseWhenE.Branch\x12)\n\telse_expr\x18\x02 \x01(\x0b\x32\x16.auron_tpu.ir.ExprNode\x1aT\n\x06\x42ranch\x12$\n\x04when\x18\x01 \x01(\x0b\x32\x16.auron_tpu.ir.ExprNode\x12$\n\x04then\x18\x02 \x01(\x0b\x32\x16.auron_tpu.ir.ExprNode\"i\n\x07InListE\x12%\n\x05\x63hild\x18\x01 \x01(\x0b\x32\x16.auron_tpu.ir.ExprNode\x12&\n\x06values\x18\x02 \x03(\x0b\x32\x16.auron_tpu.ir.LiteralE\x12\x0f\n\x07negated\x18\x03 \x01(\x08\"P\n\x05LikeE\x12%\n\x05\x63hild\x18\x01 \x01(\x0b\x32\x16.auron_tpu.ir.ExprNode\x12\x0f\n\x07pattern\x18\x02 \x01(\t\x12\x0f\n\x07negated\x18\x03 \x01(\x08\"S\n\x0bStringPredE\x12\x0c\n\x04kind\x18\x01 \x01(\t\x12%\n\x05\x63hild\x18\x02 \x01(\x0b\x32\x16.auron_tpu.ir.ExprNode\x12\x0f\n\x07pattern\x18\x03 \x01(\t\"\xa2\x01\n\x0fScalarFunctionE\x12\x0c\n\x04name\x18\x01 \x01(\t\x12$\n\x04\x61rgs\x18\x02 \x03(\x0b\x32\x16.auron_tpu.ir.ExprNode\x12\x11\n\thas_dtype\x18\x03 \x01(\x08\x12&\n\x05\x64type\x18\x04 \x01(\x0e\x32\x17.auron_tpu.ir.DataTypeP\x12\x11\n\tprecision\x18\x05 \x01(\r\x12\r\n\x05scale\x18\x06 \x01(\r\"\x18\n\x08NullaryE\x12\x0c\n\x04kind\x18\x01 \x01(\t\"\x91\x01\n\x08HostUDFE\x12\x15\n\rregistry_name\x18\x01 \x01(\t\x12$\n\x04\x61rgs\x18\x02 \x03(\x0b\x32\x16.auron_tpu.ir.ExprNode\x12&\n\x05\x64type\x18\x03 \x01(\x0e\x32\x17.auron_tpu.ir.DataTypeP\x12\x11\n\tprecision\x18\x04 \x01(\r\x12\r\n\x05scale\x18\x05 \x01(\r\"k\n\x12\x42loomMightContainE\x12%\n\x05value\x18\x01 \x01(\x0b\x32\x16.auron_tpu.ir.ExprNode\x12\x19\n\x11serialized_filter\x18\x02 \x01(\x0c\x12\x13\n\x0bresource_id\x18\x03 \x01(\t\"J\n\x10GetIndexedFieldE\x12%\n\x05\x63hild\x18\x01 \x01(\x0b\x32\x16.auron_tpu.ir.ExprNode\x12\x0f\n\x07ordinal\x18\x02 \x01(\x03\"I\n\x0fGetStructFieldE\x12%\n\x05\x63hild\x18\x01 \x01(\x0b\x32\x16.auron_tpu.ir.ExprNode\x12\x0f\n\x07ordinal\x18\x02 \x01(\x03\"Z\n\nSortOrderP\x12$\n\x04\x65xpr\x18\x01 \x01(\x0b\x32\x16.auron_tpu.ir.ExprNode\x12\x11\n\tascending\x18\x02 \x01(\x08\x12\x13\n\x0bnulls_first\x18\x03 \x01(\x08\"\x84\x01\n\x0c\x41ggFunctionP\x12\n\n\x02\x66n\x18\x01 \x01(\t\x12#\n\x03\x61rg\x18\x02 \x01(\x0b\x32\x16.auron_tpu.ir.ExprNode\x12\x10\n\x08\x64istinct\x18\x03 \x01(\x08\x12\x16\n\x0e\x65xpected_items\x18\x04 \x01(\x03\x12\x0b\n\x03\x66pp\x18\x05 \x01(\x01\x12\x0c\n\x04mode\x18\x06 \x01(\t\"\xe7\x01\n\x0fWindowFunctionP\x12\x0c\n\x04kind\x18\x01 \x01(\t\x12\n\n\x02\x66n\x18\x02 \x01(\t\x12#\n\x03\x61rg\x18\x03 \x01(\x0b\x32\x16.auron_tpu.ir.ExprNode\x12\x13\n\x06offset\x18\x04 \x01(\x03H\x00\x88\x01\x01\x12-\n\rdefault_value\x18\x05 \x01(\x0b\x32\x16.auron_tpu.ir.LiteralE\x12\x15\n\x08\x66rame_lo\x18\x06 \x01(\x03H\x01\x88\x01\x01\x12\x15\n\x08\x66rame_hi\x18\x07 \x01(\x03H\x02\x88\x01\x01\x42\t\n\x07_offsetB\x0b\n\t_frame_loB\x0b\n\t_frame_hi\"\xbd\x0b\n\x08PlanNode\x12\x35\n\x0cparquet_scan\x18\x01 \x01(\x0b\x32\x1d.auron_tpu.ir.ParquetScanNodeH\x00\x12-\n\x08orc_scan\x18\x02 \x01(\x0b\x32\x19.auron_tpu.ir.OrcScanNodeH\x00\x12\x33\n\x0bmemory_scan\x18\x03 \x01(\x0b\x32\x1c.auron_tpu.ir.MemoryScanNodeH\x00\x12\x31\n\nipc_reader\x18\x04 \x01(\x0b\x32\x1b.auron_tpu.ir.IpcReaderNodeH\x00\x12*\n\x06\x66ilter\x18\x05 \x01(\x0b\x32\x18.auron_tpu.ir.FilterNodeH\x00\x12,\n\x07project\x18\x06 \x01(\x0b\x32\x19.auron_tpu.ir.ProjectNodeH\x00\x12$\n\x03\x61gg\x18\x07 \x01(\x0b\x32\x15.auron_tpu.ir.AggNodeH\x00\x12&\n\x04sort\x18\x08 \x01(\x0b\x32\x16.auron_tpu.ir.SortNodeH\x00\x12(\n\x05limit\x18\t \x01(\x0b\x32\x17.auron_tpu.ir.LimitNodeH\x00\x12(\n\x05union\x18\n \x01(\x0b\x32\x17.auron_tpu.ir.UnionNodeH\x00\x12/\n\thash_join\x18\x0b \x01(\x0b\x32\x1a.auron_tpu.ir.HashJoinNodeH\x00\x12:\n\x0fsort_merge_join\x18\x0c \x01(\x0b\x32\x1f.auron_tpu.ir.SortMergeJoinNodeH\x00\x12\x39\n\x0e\x62roadcast_join\x18\r \x01(\x0b\x32\x1f.auron_tpu.ir.BroadcastJoinNodeH\x00\x12\x39\n\x0eshuffle_writer\x18\x0e \x01(\x0b\x32\x1f.auron_tpu.ir.ShuffleWriterNodeH\x00\x12\x41\n\x12\x62roadcast_exchange\x18\x0f \x01(\x0b\x32#.auron_tpu.ir.BroadcastExchangeNodeH\x00\x12*\n\x06window\x18\x10 \x01(\x0b\x32\x18.auron_tpu.ir.WindowNodeH\x00\x12*\n\x06\x65xpand\x18\x11 \x01(\x0b\x32\x18.auron_tpu.ir.ExpandNodeH\x00\x12.\n\x08generate\x18\x12 \x01(\x0b\x32\x1a.auron_tpu.ir.GenerateNodeH\x00\x12=\n\x10\x63oalesce_batches\x18\x13 \x01(\x0b\x32!.auron_tpu.ir.CoalesceBatchesNodeH\x00\x12\x39\n\x0erename_columns\x18\x14 \x01(\x0b\x32\x1f.auron_tpu.ir.RenameColumnsNodeH\x00\x12=\n\x10\x65mpty_partitions\x18\x15 \x01(\x0b\x32!.auron_tpu.ir.EmptyPartitionsNodeH\x00\x12\x35\n\x0cparquet_sink\x18\x16 \x01(\x0b\x32\x1d.auron_tpu.ir.ParquetSinkNodeH\x00\x12-\n\x08orc_sink\x18\x17 \x01(\x0b\x32\x19.auron_tpu.ir.OrcSinkNodeH\x00\x12(\n\x05\x64\x65\x62ug\x18\x18 \x01(\x0b\x32\x17.auron_tpu.ir.DebugNodeH\x00\x12\x31\n\nkafka_scan\x18\x19 \x01(\x0b\x32\x1b.auron_tpu.ir.KafkaScanNodeH\x00\x12<\n\x10rss_shuffle_read\x18\x1a \x01(\x0b\x32 .auron_tpu.ir.RssShuffleReadNodeH\x00\x12\x44\n\x14streaming_window_agg\x18\x1b \x01(\x0b\x32$.auron_tpu.ir.StreamingWindowAggNodeH\x00\x12\x31\n\ncross_join\x18\x1c \x01(\x0b\x32\x1b.auron_tpu.ir.CrossJoinNodeH\x00\x42\x06\n\x04node\"\x98\x01\n\x0fParquetScanNode\x12\r\n\x05\x66iles\x18\x01 \x03(\t\x12%\n\x06schema\x18\x02 \x01(\x0b\x32\x15.auron_tpu.ir.SchemaP\x12\x0f\n\x07\x63olumns\x18\x03 \x03(\t\x12*\n\npredicates\x18\x04 \x03(\x0b\x32\x16.auron_tpu.ir.ExprNode\x12\x12\n\nbatch_rows\x18\x05 \x01(\r\"h\n\x0bOrcScanNode\x12\r\n\x05\x66iles\x18\x01 \x03(\t\x12%\n\x06schema\x18\x02 \x01(\x0b\x32\x15.auron_tpu.ir.SchemaP\x12\x0f\n\x07\x63olumns\x18\x03 \x03(\t\x12\x12\n\nbatch_rows\x18\x04 \x01(\r\"8\n\x0eMemoryScanNode\x12\x12\n\ntable_name\x18\x01 \x01(\t\x12\x12\n\nbatch_rows\x18\x02 \x01(\r\"K\n\rIpcReaderNode\x12\x13\n\x0bresource_id\x18\x01 \x01(\t\x12%\n\x06schema\x18\x02 \x01(\x0b\x32\x15.auron_tpu.ir.SchemaP\"_\n\nFilterNode\x12%\n\x05\x63hild\x18\x01 \x01(\x0b\x32\x16.auron_tpu.ir.PlanNode\x12*\n\npredicates\x18\x02 \x03(\x0b\x32\x16.auron_tpu.ir.ExprNode\"j\n\x0bProjectNode\x12%\n\x05\x63hild\x18\x01 \x01(\x0b\x32\x16.auron_tpu.ir.PlanNode\x12%\n\x05\x65xprs\x18\x02 \x03(\x0b\x32\x16.auron_tpu.ir.ExprNode\x12\r\n\x05names\x18\x03 \x03(\t\"\xbd\x01\n\x07\x41ggNode\x12%\n\x05\x63hild\x18\x01 \x01(\x0b\x32\x16.auron_tpu.ir.PlanNode\x12+\n\x0bgroup_exprs\x18\x02 \x03(\x0b\x32\x16.auron_tpu.ir.ExprNode\x12(\n\x04\x61ggs\x18\x03 \x03(\x0b\x32\x1a.auron_tpu.ir.AggFunctionP\x12\x0c\n\x04mode\x18\x04 \x01(\t\x12\x13\n\x0bgroup_names\x18\x05 \x03(\t\x12\x11\n\tagg_names\x18\x06 \x03(\t\"o\n\x08SortNode\x12%\n\x05\x63hild\x18\x01 \x01(\x0b\x32\x16.auron_tpu.ir.PlanNode\x12-\n\x0bsort_orders\x18\x02 \x03(\x0b\x32\x18.auron_tpu.ir.SortOrderP\x12\r\n\x05\x66\x65tch\x18\x03 \x01(\x03\"A\n\tLimitNode\x12%\n\x05\x63hild\x18\x01 \x01(\x0b\x32\x16.auron_tpu.ir.PlanNode\x12\r\n\x05limit\x18\x02 \x01(\x03\"5\n\tUnionNode\x12(\n\x08\x63hildren\x18\x01 \x03(\x0b\x32\x16.auron_tpu.ir.PlanNode\"\xc7\x01\n\x0cHashJoinNode\x12%\n\x05probe\x18\x01 \x01(\x0b\x32\x16.auron_tpu.ir.PlanNode\x12%\n\x05\x62uild\x18\x02 \x01(\x0b\x32\x16.auron_tpu.ir.PlanNode\x12*\n\nprobe_keys\x18\x03 \x03(\x0b\x32\x16.auron_tpu.ir.ExprNode\x12*\n\nbuild_keys\x18\x04 \x03(\x0b\x32\x16.auron_tpu.ir.ExprNode\x12\x11\n\tjoin_type\x18\x05 \x01(\t\"\xcc\x01\n\x11SortMergeJoinNode\x12%\n\x05probe\x18\x01 \x01(\x0b\x32\x16.auron_tpu.ir.PlanNode\x12%\n\x05\x62uild\x18\x02 \x01(\x0b\x32\x16.auron_tpu.ir.PlanNode\x12*\n\nprobe_keys\x18\x03 \x03(\x0b\x32\x16.auron_tpu.ir.ExprNode\x12*\n\nbuild_keys\x18\x04 \x03(\x0b\x32\x16.auron_tpu.ir.ExprNode\x12\x11\n\tjoin_type\x18\x05 \x01(\t\"\xed\x01\n\x11\x42roadcastJoinNode\x12%\n\x05probe\x18\x01 \x01(\x0b\x32\x16.auron_tpu.ir.PlanNode\x12\x19\n\x11\x62uild_resource_id\x18\x02 \x01(\t\x12+\n\x0c\x62uild_schema\x18\x03 \x01(\x0b\x32\x15.auron_tpu.ir.SchemaP\x12*\n\nprobe_keys\x18\x04 \x03(\x0b\x32\x16.auron_tpu.ir.ExprNode\x12*\n\nbuild_keys\x18\x05 \x03(\x0b\x32\x16.auron_tpu.ir.ExprNode\x12\x11\n\tjoin_type\x18\x06 \x01(\t\"\x9b\x01\n\rCrossJoinNode\x12%\n\x05probe\x18\x01 \x01(\x0b\x32\x16.auron_tpu.ir.PlanNode\x12%\n\x05\x62uild\x18\x02 \x01(\x0b\x32\x16.auron_tpu.ir.PlanNode\x12\x11\n\tjoin_type\x18\x03 \x01(\t\x12)\n\tcondition\x18\x04 \x01(\x0b\x32\x16.auron_tpu.ir.ExprNode\"\x90\x01\n\rPartitioningP\x12\x0c\n\x04kind\x18\x01 \x01(\t\x12\x16\n\x0enum_partitions\x18\x02 \x01(\r\x12)\n\thash_keys\x18\x03 \x03(\x0b\x32\x16.auron_tpu.ir.ExprNode\x12.\n\x0crange_orders\x18\x04 \x03(\x0b\x32\x18.auron_tpu.ir.SortOrderP\"\xc9\x01\n\x11ShuffleWriterNode\x12%\n\x05\x63hild\x18\x01 \x01(\x0b\x32\x16.auron_tpu.ir.PlanNode\x12\x31\n\x0cpartitioning\x18\x02 \x01(\x0b\x32\x1b.auron_tpu.ir.PartitioningP\x12\x1a\n\x12output_resource_id\x18\x03 \x01(\t\x12\x18\n\x10input_partitions\x18\x04 \x01(\r\x12\x10\n\x08rss_root\x18\x05 \x01(\t\x12\x12\n\nshuffle_id\x18\x06 \x01(\r\"y\n\x12RssShuffleReadNode\x12\x10\n\x08rss_root\x18\x01 \x01(\t\x12\x12\n\nshuffle_id\x18\x02 \x01(\r\x12%\n\x06schema\x18\x03 \x01(\x0b\x32\x15.auron_tpu.ir.SchemaP\x12\x16\n\x0enum_partitions\x18\x04 \x01(\r\"t\n\x15\x42roadcastExchangeNode\x12%\n\x05\x63hild\x18\x01 \x01(\x0b\x32\x16.auron_tpu.ir.PlanNode\x12\x1a\n\x12output_resource_id\x18\x02 \x01(\t\x12\x18\n\x10input_partitions\x18\x03 \x01(\r\"\xea\x01\n\nWindowNode\x12%\n\x05\x63hild\x18\x01 \x01(\x0b\x32\x16.auron_tpu.ir.PlanNode\x12,\n\x0cpartition_by\x18\x02 \x03(\x0b\x32\x16.auron_tpu.ir.ExprNode\x12*\n\x08order_by\x18\x03 \x03(\x0b\x32\x18.auron_tpu.ir.SortOrderP\x12\x30\n\tfunctions\x18\x04 \x03(\x0b\x32\x1d.auron_tpu.ir.WindowFunctionP\x12\x14\n\x0coutput_names\x18\x05 \x03(\t\x12\x13\n\x0bgroup_limit\x18\x06 \x01(\x03\"\xb1\x01\n\nExpandNode\x12%\n\x05\x63hild\x18\x01 \x01(\x0b\x32\x16.auron_tpu.ir.PlanNode\x12\x38\n\x0bprojections\x18\x02 \x03(\x0b\x32#.auron_tpu.ir.ExpandNode.Projection\x12\r\n\x05names\x18\x03 \x03(\t\x1a\x33\n\nProjection\x12%\n\x05\x65xprs\x18\x01 \x03(\x0b\x32\x16.auron_tpu.ir.ExprNode\"\xe3\x01\n\x0cGenerateNode\x12%\n\x05\x63hild\x18\x01 \x01(\x0b\x32\x16.auron_tpu.ir.PlanNode\x12\x0c\n\x04kind\x18\x02 \x01(\t\x12)\n\tgenerator\x18\x03 \x01(\x0b\x32\x16.auron_tpu.ir.ExprNode\x12\x13\n\x0bjson_fields\x18\x04 \x03(\t\x12\x1a\n\x12udtf_registry_name\x18\x05 \x01(\t\x12\x1d\n\x15required_child_output\x18\x06 \x03(\x05\x12\r\n\x05outer\x18\x07 \x01(\x08\x12\x14\n\x0coutput_names\x18\x08 \x03(\t\"Q\n\x13\x43oalesceBatchesNode\x12%\n\x05\x63hild\x18\x01 \x01(\x0b\x32\x16.auron_tpu.ir.PlanNode\x12\x13\n\x0btarget_rows\x18\x02 \x01(\r\"I\n\x11RenameColumnsNode\x12%\n\x05\x63hild\x18\x01 \x01(\x0b\x32\x16.auron_tpu.ir.PlanNode\x12\r\n\x05names\x18\x02 \x03(\t\"T\n\x13\x45mptyPartitionsNode\x12%\n\x06schema\x18\x01 \x01(\x0b\x32\x15.auron_tpu.ir.SchemaP\x12\x16\n\x0enum_partitions\x18\x02 \x01(\r\"q\n\x0fParquetSinkNode\x12%\n\x05\x63hild\x18\x01 \x01(\x0b\x32\x16.auron_tpu.ir.PlanNode\x12\x0c\n\x04path\x18\x02 \x01(\t\x12\x14\n\x0cpartition_by\x18\x03 \x03(\t\x12\x13\n\x0b\x63ompression\x18\x04 \x01(\t\"W\n\x0bOrcSinkNode\x12%\n\x05\x63hild\x18\x01 \x01(\x0b\x32\x16.auron_tpu.ir.PlanNode\x12\x0c\n\x04path\x18\x02 \x01(\t\x12\x13\n\x0b\x63ompression\x18\x03 \x01(\t\"A\n\tDebugNode\x12%\n\x05\x63hild\x18\x01 \x01(\x0b\x32\x16.auron_tpu.ir.PlanNode\x12\r\n\x05label\x18\x02 \x01(\t\"\x8f\x01\n\rKafkaScanNode\x12\r\n\x05topic\x18\x01 \x01(\t\x12\x11\n\tbootstrap\x18\x02 \x01(\t\x12%\n\x06schema\x18\x03 \x01(\x0b\x32\x15.auron_tpu.ir.SchemaP\x12\x0e\n\x06\x66ormat\x18\x04 \x01(\t\x12\x13\n\x0bmax_batches\x18\x05 \x01(\r\x12\x10\n\x08group_id\x18\x06 \x01(\t\"\xf9\x01\n\x16StreamingWindowAggNode\x12%\n\x05\x63hild\x18\x01 \x01(\x0b\x32\x16.auron_tpu.ir.PlanNode\x12\x10\n\x08time_col\x18\x02 \x01(\r\x12\x11\n\twindow_us\x18\x03 \x01(\x03\x12\x14\n\x0cooo_bound_us\x18\x04 \x01(\x03\x12+\n\x0bgroup_exprs\x18\x05 \x03(\x0b\x32\x16.auron_tpu.ir.ExprNode\x12(\n\x04\x61ggs\x18\x06 \x03(\x0b\x32\x1a.auron_tpu.ir.AggFunctionP\x12\x13\n\x0bgroup_names\x18\x07 \x03(\t\x12\x11\n\tagg_names\x18\x08 \x03(\t\"\x87\x01\n\x0eTaskDefinition\x12\x10\n\x08stage_id\x18\x01 \x01(\x05\x12\x14\n\x0cpartition_id\x18\x02 \x01(\x05\x12\x0f\n\x07task_id\x18\x03 \x01(\x03\x12\x16\n\x0enum_partitions\x18\x04 \x01(\x05\x12$\n\x04plan\x18\x05 \x01(\x0b\x32\x16.auron_tpu.ir.PlanNode*\xe7\x01\n\tDataTypeP\x12\x0b\n\x07\x44T_NULL\x10\x00\x12\x0b\n\x07\x44T_BOOL\x10\x01\x12\x0b\n\x07\x44T_INT8\x10\x02\x12\x0c\n\x08\x44T_INT16\x10\x03\x12\x0c\n\x08\x44T_INT32\x10\x04\x12\x0c\n\x08\x44T_INT64\x10\x05\x12\x0e\n\nDT_FLOAT32\x10\x06\x12\x0e\n\nDT_FLOAT64\x10\x07\x12\r\n\tDT_DATE32\x10\x08\x12\x13\n\x0f\x44T_TIMESTAMP_US\x10\t\x12\x0e\n\nDT_DECIMAL\x10\n\x12\r\n\tDT_STRING\x10\x0b\x12\x0b\n\x07\x44T_LIST\x10\x0c\x12\n\n\x06\x44T_MAP\x10\r\x12\r\n\tDT_STRUCT\x10\x0e\x62\x06proto3')

_builder.BuildMessageAndEnumDescriptors(DESCRIPTOR, globals())
_builder.BuildTopDescriptorsAndMessages(DESCRIPTOR, 'auron_pb2', globals())
if _descriptor._USE_C_DESCRIPTORS == False:

  DESCRIPTOR._options = None
  _DATATYPEP._serialized_start=8909
  _DATATYPEP._serialized_end=9140
  _FIELDP._serialized_start=30
  _FIELDP._serialized_end=283
  _SCHEMAP._serialized_start=285
  _SCHEMAP._serialized_end=332
  _EXPRNODE._serialized_start=335
  _EXPRNODE._serialized_end=1120
  _SCALARSUBQUERYE._serialized_start=1123
  _SCALARSUBQUERYE._serialized_end=1265
  _COLUMNREFE._serialized_start=1267
  _COLUMNREFE._serialized_end=1308
  _LITERALE._serialized_start=1311
  _LITERALE._serialized_end=1466
  _BINARYE._serialized_start=1468
  _BINARYE._serialized_end=1566
  _UNARYE._serialized_start=1568
  _UNARYE._serialized_end=1627
  _CASTE._serialized_start=1630
  _CASTE._serialized_end=1782
  _CASEWHENE._serialized_start=1785
  _CASEWHENE._serialized_end=1975
  _CASEWHENE_BRANCH._serialized_start=1891
  _CASEWHENE_BRANCH._serialized_end=1975
  _INLISTE._serialized_start=1977
  _INLISTE._serialized_end=2082
  _LIKEE._serialized_start=2084
  _LIKEE._serialized_end=2164
  _STRINGPREDE._serialized_start=2166
  _STRINGPREDE._serialized_end=2249
  _SCALARFUNCTIONE._serialized_start=2252
  _SCALARFUNCTIONE._serialized_end=2414
  _NULLARYE._serialized_start=2416
  _NULLARYE._serialized_end=2440
  _HOSTUDFE._serialized_start=2443
  _HOSTUDFE._serialized_end=2588
  _BLOOMMIGHTCONTAINE._serialized_start=2590
  _BLOOMMIGHTCONTAINE._serialized_end=2697
  _GETINDEXEDFIELDE._serialized_start=2699
  _GETINDEXEDFIELDE._serialized_end=2773
  _GETSTRUCTFIELDE._serialized_start=2775
  _GETSTRUCTFIELDE._serialized_end=2848
  _SORTORDERP._serialized_start=2850
  _SORTORDERP._serialized_end=2940
  _AGGFUNCTIONP._serialized_start=2943
  _AGGFUNCTIONP._serialized_end=3075
  _WINDOWFUNCTIONP._serialized_start=3078
  _WINDOWFUNCTIONP._serialized_end=3309
  _PLANNODE._serialized_start=3312
  _PLANNODE._serialized_end=4781
  _PARQUETSCANNODE._serialized_start=4784
  _PARQUETSCANNODE._serialized_end=4936
  _ORCSCANNODE._serialized_start=4938
  _ORCSCANNODE._serialized_end=5042
  _MEMORYSCANNODE._serialized_start=5044
  _MEMORYSCANNODE._serialized_end=5100
  _IPCREADERNODE._serialized_start=5102
  _IPCREADERNODE._serialized_end=5177
  _FILTERNODE._serialized_start=5179
  _FILTERNODE._serialized_end=5274
  _PROJECTNODE._serialized_start=5276
  _PROJECTNODE._serialized_end=5382
  _AGGNODE._serialized_start=5385
  _AGGNODE._serialized_end=5574
  _SORTNODE._serialized_start=5576
  _SORTNODE._serialized_end=5687
  _LIMITNODE._serialized_start=5689
  _LIMITNODE._serialized_end=5754
  _UNIONNODE._serialized_start=5756
  _UNIONNODE._serialized_end=5809
  _HASHJOINNODE._serialized_start=5812
  _HASHJOINNODE._serialized_end=6011
  _SORTMERGEJOINNODE._serialized_start=6014
  _SORTMERGEJOINNODE._serialized_end=6218
  _BROADCASTJOINNODE._serialized_start=6221
  _BROADCASTJOINNODE._serialized_end=6458
  _CROSSJOINNODE._serialized_start=6461
  _CROSSJOINNODE._serialized_end=6616
  _PARTITIONINGP._serialized_start=6619
  _PARTITIONINGP._serialized_end=6763
  _SHUFFLEWRITERNODE._serialized_start=6766
  _SHUFFLEWRITERNODE._serialized_end=6967
  _RSSSHUFFLEREADNODE._serialized_start=6969
  _RSSSHUFFLEREADNODE._serialized_end=7090
  _BROADCASTEXCHANGENODE._serialized_start=7092
  _BROADCASTEXCHANGENODE._serialized_end=7208
  _WINDOWNODE._serialized_start=7211
  _WINDOWNODE._serialized_end=7445
  _EXPANDNODE._serialized_start=7448
  _EXPANDNODE._serialized_end=7625
  _EXPANDNODE_PROJECTION._serialized_start=7574
  _EXPANDNODE_PROJECTION._serialized_end=7625
  _GENERATENODE._serialized_start=7628
  _GENERATENODE._serialized_end=7855
  _COALESCEBATCHESNODE._serialized_start=7857
  _COALESCEBATCHESNODE._serialized_end=7938
  _RENAMECOLUMNSNODE._serialized_start=7940
  _RENAMECOLUMNSNODE._serialized_end=8013
  _EMPTYPARTITIONSNODE._serialized_start=8015
  _EMPTYPARTITIONSNODE._serialized_end=8099
  _PARQUETSINKNODE._serialized_start=8101
  _PARQUETSINKNODE._serialized_end=8214
  _ORCSINKNODE._serialized_start=8216
  _ORCSINKNODE._serialized_end=8303
  _DEBUGNODE._serialized_start=8305
  _DEBUGNODE._serialized_end=8370
  _KAFKASCANNODE._serialized_start=8373
  _KAFKASCANNODE._serialized_end=8516
  _STREAMINGWINDOWAGGNODE._serialized_start=8519
  _STREAMINGWINDOWAGGNODE._serialized_end=8768
  _TASKDEFINITION._serialized_start=8771
  _TASKDEFINITION._serialized_end=8906
# @@protoc_insertion_point(module_scope)
