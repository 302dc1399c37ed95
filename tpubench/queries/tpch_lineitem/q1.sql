SELECT l_returnflag, l_linestatus,
       SUM(l_quantity), SUM(l_extendedprice),
       SUM(l_extendedprice * (1 - l_discount)),
       SUM(l_extendedprice * (1 - l_discount) * (1 + l_tax)),
       AVG(l_quantity), AVG(l_extendedprice), AVG(l_discount), COUNT(1)
FROM lineitem
WHERE l_shipdate <= '{cutoff}'
GROUP BY l_returnflag, l_linestatus
